"""The MiniCPM-SALA block (``minicpm_sala``): the reader, the two mixers, the
compressed-key array and the choice of blocks, the programs of
engine/hybrid.py through the runner and the engine, held to
benchmark/references/minicpm_sala.py on seeded weights at the rehearsal's
size (hidden 64, layers S L L S L L S, blocks of 8 keys of which a query
keeps 6, so a 200-token prompt is well past where blocks are dropped)."""

from __future__ import annotations

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
from dynamo_tpu.engine import hybrid, model, recurrence  # noqa: E402
from dynamo_tpu.engine.backends import Backends  # noqa: E402
from dynamo_tpu.engine.config import (EngineConfig, MiniCPMSALASpec,  # noqa: E402
                                      ModelSpec, UnsupportedBlockError,
                                      block_refusals)
from dynamo_tpu.engine.engine import TPUEngine  # noqa: E402
from dynamo_tpu.engine.runner import (PK_CAP, PK_LOGPROB, PK_POS,  # noqa: E402
                                      PK_PREFIX, PK_SEQLEN, PK_TOPP,
                                      ModelRunner, PrefillSeq)
from dynamo_tpu.llm.protocols import PreprocessedRequest  # noqa: E402
from dynamo_tpu.runtime.context import Context  # noqa: E402

ref = manifest.load_module("references", "minicpm_sala")
counts = manifest.load_module("rooflines", "minicpm_sala")
CONFIG = manifest.load_json(os.path.join(
    manifest.BENCH, "configs", "minicpm-sala-9b-int8.json"))
PAGE = 16
TOY = {**{k: v for k, v in CONFIG.items()
          if not isinstance(v, (dict, list)) or k == "mixer_types"},
       **CONFIG["rehearsal_model"], "chunk_size": 8}


def read_spec(cfg: dict) -> ModelSpec:
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "config.json")
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(cfg, fh)
        return dataclasses.replace(ModelSpec.from_hf_config(path),
                                   name="sala")


def seeded_params(spec, seed: int):
    """init_params, then every norm's weight drawn around 1 (a norm left
    out, or a weight of the wrong width, is then another number)."""
    params = model.init_params(spec, jax.random.key(seed))
    key = jax.random.key(seed + 100)
    for i, (name, leaf) in enumerate(sorted(params["layers"].items())):
        if name.endswith("_norm"):
            params["layers"][name] = (1.0 + 0.3 * jax.random.normal(
                jax.random.fold_in(key, i), leaf.shape)).astype(jnp.bfloat16)
    return params


SPEC = read_spec(TOY)
PARAMS = seeded_params(SPEC, 11)


def config(**kw) -> EngineConfig:
    defaults = dict(model=SPEC, page_size=PAGE, num_pages=128,
                    max_pages_per_seq=32, max_num_seqs=4,
                    prefill_buckets=(32, 64, 128, 256),
                    max_prefill_tokens=256, attention_backend="xla",
                    decode_window=4, pipeline_depth=2)
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


#: Largest median and root mean square (nat) of served logprobs from the
#: reference's that pass here: bfloat16 activations against float32 at this
#: size read 0.002 and 0.003 (three prompts, 20 to 48 tokens), the nearest
#: control (``select=false``) 0.008 and 0.017.
NEAR = {"median": 0.005, "rms": 0.009}


def distance(a, b) -> dict:
    d = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
    return {"median": float(np.median(d)),
            "rms": float(np.sqrt(np.mean(d * d)))}


def near(a, b) -> bool:
    got = distance(a, b)
    return all(got[k] <= NEAR[k] for k in NEAR)


# -- the reader ------------------------------------------------------------------

def catalog_row() -> dict:
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path, encoding="utf-8") as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    return next(r for r in rows if r["name"] == "MiniCPM-SALA")


def test_the_reader_makes_the_catalog_row_s_spec():
    spec = read_spec(catalog_row()["config"])
    assert isinstance(spec, MiniCPMSALASpec) and spec.recurrent
    groups = hybrid.groups_of(spec)
    assert (spec.num_layers, spec.ssm_layers, spec.pool_layers,
            spec.expert_layers) == (32, 24, 8, 0)
    assert [a // 2 for a in groups.attn_layer if a >= 0] \
        == [0, 9, 16, 17, 22, 29, 30, 31]
    assert [i for i in groups.mixer_index if i >= 0] == list(range(24))
    assert spec.kv_entry == (2, (128, 128)) and spec.compressed_keys
    assert spec.kv_bytes_per_token() == 8 * (1024 + 32) == 8448
    assert spec.ssm_state_shapes == ((32, 128, 128), None)
    assert spec.ssm_state_bytes_per_row == 24 * 32 * 128 * 128 * 4
    assert spec.comp_key_shape(100, 128) == (8, 2, 100, 8, 128)
    assert (spec.sparse_kernel, spec.sparse_stride, spec.sparse_block,
            spec.sparse_topk, spec.sparse_init_blocks, spec.sparse_window) \
        == (32, 16, 64, 64, 1, 2048)
    assert spec.scale_emb == 12.0 and spec.logit_divisor == 16.0
    assert abs(spec.residual_scale - 1.4 / 32 ** 0.5) < 1e-12
    shapes = model.param_shapes(spec)["layers"]
    assert shapes["ssm_w_in"] == (24, 4096, 4 * 4096)
    assert shapes["ssm_w_out"] == (24, 4096, 4096)
    assert shapes["wk"] == (8, 4096, 256) and shapes["wz"] == (8, 4096, 4096)
    assert shapes["w_gate"] == (32, 4096, 16384)
    assert shapes["mixer_norm"] == (64, 4096)
    assert spec.num_params() == 9_477_206_016
    # A page is whole blocks wherever it is resolved.
    assert EngineConfig(model=spec).page_size % 64 == 0


def test_the_configuration_s_bytes_by_the_roofline_module():
    """ISSUE 45's table from the configuration file alone."""
    assert counts.kinds(CONFIG) == {"L": 24, "S": 8}
    assert counts.state_bytes_per_row(CONFIG) == 50_331_648
    assert counts.kv_bytes_per_token(CONFIG) == 8448
    sizes = counts._sizes(CONFIG)
    values = lambda ms: sum(v for v, _ in ms)  # noqa: E731
    lightning = values(sizes["lightning"]) + values(sizes["mlp"])
    attention = values(sizes["attention"]) + values(sizes["mlp"])
    assert round(lightning / 1e6, 1) == 285.2
    assert round(attention / 1e6, 1) == 253.8
    assert round((24 * lightning + 8 * attention) / 1e9, 3) == 8.875
    assert values(sizes["head"]) == 73448 * 4096
    step = counts.decode_step_bytes(CONFIG, "int8", 1, 24, 24 * 7000)
    assert 9.4e9 + 24 * 2 * 50.3e6 < step < 9.6e9 + 24 * 2 * 50.4e6 + 1.0e9
    # The choice bounds what attention reads: 24 rows of 15,000 keys read
    # no more than 24 rows of 4,096.
    deep = counts.decode_step_bytes(CONFIG, "int8", 1, 24, 24 * 15000)
    kept = counts.decode_step_bytes(CONFIG, "int8", 1, 24, 24 * 4096)
    assert deep - kept == 8 * 24 * (15000 - 4096) * 2 * 16
    assert counts.sparse_attention_counts(CONFIG, 1000.0)[0] == 1000 * 1024
    assert counts.index_counts(CONFIG, "int8", 24, 1600.0)[0] == 1600 * 32


@pytest.mark.parametrize("key,value,names", [
    ("attn_use_rope", True, "rotate nothing"),
    ("lightning_use_rope", False, "rotate q and k"),
    ("qk_norm", False, "RMS-normalised"),
    ("use_output_norm", False, "ahead of its gate"),
    ("use_output_gate", False, "gated"),
    ("attn_use_output_gate", False, "gated"),
    ("hidden_act", "gelu", "SwiGLU"),
    ("lightning_nkv", 2, "share"),
    ("mixer_types", ["minicpm4"] * 6 + ["mamba"], "mixer_types"),
    ("sparse_config", {**TOY["sparse_config"], "kernel_size": 6},
     "two strides"),
    ("sparse_config", {**TOY["sparse_config"], "window_size": 8},
     "under two blocks"),
])
def test_the_reader_refuses_what_is_not_written_down(key, value, names):
    with pytest.raises(UnsupportedBlockError, match=names):
        read_spec({**TOY, key: value})


# -- refusals ------------------------------------------------------------------

@pytest.mark.parametrize("asked,names", [
    (dict(spec_decode="ngram", spec_k=2), "choice of blocks a position"),
    (dict(spec_decode="ngram", spec_k=2), "rejected draft"),
    (dict(host_cache_pages=8), "no tier holds"),
    (dict(tp=2), "ONE KV head"),
    (dict(tp=2), "partitioning rule"),
    (dict(quant_kv="int8"), "mean of int8 rows"),
    (dict(max_adapters=2), "have none of them"),
    (dict(page_size=4), "whole blocks"),
])
def test_each_engine_path_that_lacks_the_arrays_is_refused(asked, names):
    refusals = block_refusals(SPEC, config(**asked))
    assert any(names in str(r) for r in refusals), [str(r) for r in refusals]
    with pytest.raises(UnsupportedBlockError):
        ModelRunner(config(**asked), params=PARAMS)


@pytest.mark.parametrize("asked,names", [
    (dict(kv_transfer=True), "compressed-key array"),
    (dict(kv_transfer=True), "recurrent state"),
    (dict(checkpoint=True), "tensor-name map"),
    (dict(embeddings=True), "token rows alone"),
])
def test_a_parcel_a_checkpoint_and_embeddings_are_refused(asked, names):
    assert any(names in str(r) for r in block_refusals(SPEC, **asked))


def test_the_other_blocks_state_no_compressed_keys():
    dense = ModelSpec()
    assert not dense.compressed_keys and dense.residual_scale == 1.0
    assert dense.kv_bytes_per_token() == (
        2 * dense.num_layers * dense.num_kv_heads * dense.head_dim * 2)
    assert not [r for r in block_refusals(dense, EngineConfig(
        model=dense, page_size=16, num_pages=32))]


# -- the lightning mixer ---------------------------------------------------------

def _lightning_inputs(rows: int, tokens: int, seed: int):
    key = jax.random.key(seed)
    lp = jax.tree.map(lambda a: a[1], {
        k: v for k, v in PARAMS["layers"].items() if k.startswith("ssm_")})
    h = jax.random.normal(key, (rows, tokens, SPEC.hidden_size),
                          jnp.bfloat16)
    s_shape, _ = SPEC.ssm_state_shapes
    state = 0.5 * jax.random.normal(jax.random.fold_in(key, 1),
                                    (rows, *s_shape), jnp.float32)
    return lp, h, state


@pytest.mark.parametrize("tokens,limit", [(5, 2048), (24, 2048), (64, 16),
                                          (48, 96)])
def test_the_chunked_scan_equals_the_step_recurrence(tokens, limit):
    """Prefill's chunks (8 tokens a chunk; ``limit`` tokens at once: one
    row's blocks in turn, or groups of rows) against one token at a time,
    with rows of unequal length: outputs and the state each row leaves."""
    rows = 3
    lp, h, state = _lightning_inputs(rows, tokens, 3)
    lens = jnp.asarray([tokens, tokens - 3, 1])
    valid = jnp.arange(tokens)[None, :] < lens[:, None]
    positions = jnp.broadcast_to(jnp.arange(tokens)[None, :] + 5,
                                 (rows, tokens))
    parts = hybrid._lightning_project(h, lp, SPEC, positions)
    y, got_state = hybrid.lightning_recurrence(parts, SPEC, state, valid,
                                               limit)
    got = hybrid._lightning_out(y, parts, lp, SPEC)
    want_state, outs = state, []
    for t in range(tokens):
        out, want_state = hybrid.lightning_step(
            h[:, t], lp, SPEC, want_state, positions[:, t], valid[:, t])
        outs.append(out)
    want = jnp.stack(outs, axis=1)
    on = np.asarray(valid)
    scale = float(jnp.abs(want.astype(jnp.float32)).max())
    assert np.abs(np.asarray(got, np.float32)
                  - np.asarray(want, np.float32))[on].max() < 0.03 * scale
    np.testing.assert_allclose(np.asarray(got_state), np.asarray(want_state),
                               rtol=0.02, atol=0.01 * float(
                                   jnp.abs(want_state).max()))


def test_the_decay_is_the_law_s():
    lam = np.asarray(hybrid.lightning_decay(SPEC))
    assert lam.shape == (4,) and np.all(np.diff(lam) > 0)
    np.testing.assert_allclose(
        lam, np.exp(-2.0 ** (-8.0 * np.arange(1, 5) / 4)), rtol=1e-6)
    full = np.asarray(hybrid.lightning_decay(read_spec(
        {**TOY, "lightning_nh": 32, "lightning_nkv": 32,
         "lightning_head_dim": 16, "hidden_size": 512,
         "num_attention_heads": 32})))
    assert abs(full[-1] - np.exp(-2.0 ** -8)) < 1e-7


def _bits(a) -> np.ndarray:
    return np.asarray(a).view(np.int32)


@pytest.mark.parametrize("live", [[True, True, True, True],
                                  [False, False, False, False],
                                  [True, False, False, True],
                                  [False, True, False, False]])
def test_the_kernel_equals_the_definition_at_a_group_a_head(live):
    """engine/recurrence.py through the Pallas interpreter at groups =
    heads (a head's B and C its own), one layer of a stack of three: a live
    row's output and new state are ``state_update``'s; a dead slot (NaN in
    its state from the start) and the other layers keep theirs BITWISE."""
    rows, layer = 4, 1
    lp, h, _ = _lightning_inputs(rows, 1, 9)
    s_shape, _ = SPEC.ssm_state_shapes
    states = 0.5 * jax.random.normal(jax.random.key(8), (3, rows, *s_shape),
                                     jnp.float32)
    on = jnp.asarray(live)
    states = jnp.where(on[None, :, None, None, None], states, jnp.nan)
    parts = hybrid._lightning_project(h[:, 0], lp, SPEC,
                                      jnp.arange(rows) + 7)
    dx, bb, cc, _, da = hybrid._lightning_terms(parts, SPEC, on)
    assert bb.shape[1] == cc.shape[1] == SPEC.ssm_heads == SPEC.ssm_groups
    y_want, want = hybrid.state_update(
        jnp.nan_to_num(states[layer]), jnp.exp(da), dx, bb, cc)
    new, y = recurrence.state_step(
        states, layer, *hybrid.live_walk(on), jnp.exp(da),
        dx.reshape(rows, SPEC.ssm_heads, -1), bb, cc, interpret=True)
    keep = np.asarray(on)
    np.testing.assert_allclose(
        np.asarray(y)[keep], np.asarray(y_want).reshape(y.shape)[keep],
        rtol=1e-5, atol=1e-5)
    assert not np.asarray(y)[~keep].any()
    np.testing.assert_allclose(np.asarray(new[layer])[keep],
                               np.asarray(want)[keep], rtol=2e-6, atol=1e-6)
    np.testing.assert_array_equal(_bits(new[layer])[~keep],
                                  _bits(states[layer])[~keep])
    np.testing.assert_array_equal(_bits(new[::2]), _bits(states[::2]))


# -- the choice ------------------------------------------------------------------

def _first_layer_qk(tokens, keys: str = "own"):
    """q [S, nh, d] and k [S, nkv, d] of layer 0 (attention over chosen
    blocks; its input is the embedding on both sides), float32."""
    from benchmark.lib.reference import plain, rms_norm
    layers = PARAMS["layers"]
    eps = SPEC.rms_norm_eps
    x = ref.embedded(PARAMS, SPEC, np.asarray(tokens, np.int32))
    u = rms_norm(x, layers["mixer_norm"][0], eps)
    s = len(tokens)
    q = rms_norm((u @ plain(layers["wq"][0])).reshape(
        s, SPEC.num_heads, -1), layers["q_norm"][0], eps)
    k = rms_norm((u @ plain(layers["wk"][0])).reshape(
        s, SPEC.num_kv_heads, -1), layers["k_norm"][0], eps)
    return q, k


@pytest.mark.parametrize("tokens", [5, 40, 130],
                         ids=["fewer blocks than a query keeps",
                              "as many", "blocks are dropped"])
def test_the_chosen_blocks_are_the_reference_s(tokens):
    """``choose_blocks`` over stripes (the mean of 2 keys; a compressed key
    the mean of two stripes) against the reference's choice over the means
    of 4 keys, a query at a time: the same sets, the first block and the
    window's always among them."""
    prompt = prompt_of(tokens, 5)
    padded = -(-tokens // SPEC.sparse_block) * SPEC.sparse_block
    q, k = _first_layer_qk(prompt + [0] * (padded - tokens))
    want = ref.chosen_blocks(PARAMS, SPEC, prompt)[0]      # [S, nkv, nb]
    with jax.default_matmul_precision("highest"):
        stripes = hybrid.stripe_means(k[None], SPEC.sparse_stride)[0]
        qg = q.reshape(padded, SPEC.num_kv_heads, -1, SPEC.head_dim)
        dots = jnp.einsum("qngd,ind->qngi", qg, stripes)
        blocks, kept = hybrid.choose_blocks(dots, jnp.arange(padded) + 1,
                                            SPEC)
    nb = want.shape[-1]
    got = np.zeros((tokens, SPEC.num_kv_heads, nb), bool)
    for t in range(tokens):
        for g in range(SPEC.num_kv_heads):
            got[t, g, np.asarray(blocks[t, g])[np.asarray(kept[t, g])]] = True
    assert (got == want).mean() > 0.999, np.argwhere(got != want)[:5]
    own = np.arange(tokens) // SPEC.sparse_block
    window = SPEC.sparse_window // SPEC.sparse_block
    for t in range(tokens):
        assert got[t, :, 0].all() and got[t, :, own[t]].all()
        assert got[t, :, max(own[t] - window + 1, 0):own[t] + 1].all()
        assert got[t].sum(-1).max() == min(own[t] + 1, SPEC.sparse_topk)
        assert not got[t, :, own[t] + 1:].any()


def test_equal_scores_keep_the_lower_block():
    """Every key the same: every block scores the same, and the 6 kept are
    the first, the window's two and the LOWEST three of the rest, in the
    program and in the reference."""
    tokens = 96
    dots = jnp.zeros((tokens, 2, 2, tokens // SPEC.sparse_stride))
    blocks, kept = hybrid.choose_blocks(dots, jnp.arange(tokens) + 1, SPEC)
    assert kept[-1].all()
    assert sorted(np.asarray(blocks[-1, 0]).tolist()) == [0, 1, 2, 3, 10, 11]
    params = jax.tree.map(lambda a: a, PARAMS)
    params["layers"] = {**PARAMS["layers"],
                        "wk": jnp.zeros_like(PARAMS["layers"]["wk"])}
    want = ref.chosen_blocks(params, SPEC, prompt_of(tokens, 1))[0]
    assert np.flatnonzero(want[-1, 0]).tolist() == [0, 1, 2, 3, 10, 11]


def _blocks_by_sort(dots, n_keys, spec):
    """The choice as PR 45 made it: the same block scores, then
    ``lax.top_k`` (a sort): the set ``choose_blocks`` is held to."""
    st, bk = spec.sparse_stride, spec.sparse_block
    per = bk // st
    ns = dots.shape[-1]
    nb = ns // per
    n = n_keys[..., None, None]
    score = 0.5 * (dots[..., :-1] + dots[..., 1:]) * spec.head_dim ** -0.5
    whole = (jnp.arange(ns - 1) + 2) * st <= n[..., None]
    top = jnp.max(jnp.where(whole, score, -jnp.inf), axis=-1, keepdims=True)
    e = jnp.where(whole, jnp.exp(score - jnp.where(jnp.isfinite(top), top,
                                                   0.0)), 0.0)
    p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    group = jnp.where(whole[..., 0, :], jnp.sum(p, axis=-2), -jnp.inf)
    lead = group.shape[:-1]
    edge = jnp.full((*lead, 1), -jnp.inf)
    padded = jnp.concatenate([edge, group, edge], axis=-1)
    block = jnp.maximum(
        jnp.max(padded[..., 1:].reshape(*lead, nb, per), axis=-1),
        padded[..., 0:nb * per:per])
    ids = jnp.arange(nb)
    own = (n - 1) // bk
    exists = ids <= own
    forced = ((ids < spec.sparse_init_blocks)
              | (own - ids < spec.sparse_window // bk))
    block = jnp.where(forced, jnp.inf, block)
    block = jnp.where(exists, block, -jnp.inf)
    _, blocks = jax.lax.top_k(
        jnp.where(exists, jnp.maximum(block, -1e30), -jnp.inf),
        min(spec.sparse_topk, nb))
    kept = jnp.take_along_axis(jnp.broadcast_to(exists, block.shape), blocks,
                               axis=-1)
    return blocks, kept


#: The choice at the published sizes: 64 of 256 blocks of 64 keys.
PUBLISHED = dataclasses.replace(
    SPEC, sparse_kernel=32, sparse_stride=16, sparse_block=64,
    sparse_topk=64, sparse_window=2048)


def _scores(case: str, spec, rows: int, seed: int):
    """(dots [rows, 2, 2, NS], n_keys [rows]) of a case of the rank."""
    rng = np.random.default_rng(seed)
    per = spec.sparse_block // spec.sparse_stride
    nb = 4 * spec.sparse_topk
    ns = nb * per
    dots = rng.normal(size=(rows, 2, 2, ns)) * 8
    n_keys = rng.integers(nb * spec.sparse_block // 2,
                          nb * spec.sparse_block, size=rows) + 1
    if case == "ties across the rank":
        # Half as many blocks as are kept stand out, every other block
        # scores the same: rank K falls among equals.
        high = rng.permuted(np.broadcast_to(
            np.arange(nb) < spec.sparse_topk // 2, (rows, 2, 1, nb)), axis=-1)
        dots = np.repeat(np.where(high, 40.0, 0.0), per, axis=-1) \
            + np.zeros_like(dots)
    elif case == "all equal":
        dots = np.zeros_like(dots)
    elif case == "fewer blocks than are kept":
        n_keys = rng.integers(1, spec.sparse_topk * spec.sparse_block // 2,
                              size=rows)
    elif case == "blocks that do not exist":
        # From a query's first key to a row that fills the table.
        n_keys = np.linspace(1, nb * spec.sparse_block, rows).astype(int)
    elif case == "a table narrower than the kept":
        dots = dots[..., :spec.sparse_topk // 2 * per]
        n_keys = rng.integers(1, spec.sparse_topk // 2 * spec.sparse_block,
                              size=rows)
    return jnp.asarray(dots, jnp.float32), jnp.asarray(n_keys, jnp.int32)


@pytest.mark.parametrize("spec", [SPEC, PUBLISHED],
                         ids=["6 of 24 blocks", "64 of 256 blocks"])
@pytest.mark.parametrize("case", [
    "random", "ties across the rank", "all equal",
    "fewer blocks than are kept", "blocks that do not exist",
    "a table narrower than the kept"])
def test_the_rank_by_counts_keeps_top_k_s_set(case, spec):
    """``choose_blocks`` finds its set by counts (block i stays when fewer
    than K beat it): ``lax.top_k``'s set exactly, its tie rule with it,
    never more than K, in rising order with the blocks that do not exist
    last and not ``kept``."""
    dots, n_keys = _scores(case, spec, rows=12, seed=len(case))
    blocks, kept = (np.asarray(a) for a in
                    jax.jit(lambda d, n: hybrid.choose_blocks(d, n, spec))(
                        dots, n_keys))
    want, want_kept = (np.asarray(a) for a in
                       _blocks_by_sort(dots, n_keys, spec))
    nb = dots.shape[-1] * spec.sparse_stride // spec.sparse_block
    assert blocks.shape == want.shape == (12, 2, min(spec.sparse_topk, nb))
    assert blocks.dtype == np.int32 and kept.dtype == bool
    assert (np.diff(blocks, axis=-1) > 0).all()          # rising, no repeat
    np.testing.assert_array_equal(np.sort(want, axis=-1), blocks)
    own = (np.asarray(n_keys) - 1) // spec.sparse_block
    np.testing.assert_array_equal(kept, blocks <= own[:, None, None])
    assert (kept.sum(-1) == want_kept.sum(-1)).all()


# -- the runner ------------------------------------------------------------------

def _window(runner, rows: dict, steps: int):
    """One window over ``rows`` {slot: (position, pages)}; returns the
    tokens and logprobs [steps, slots] and what the window counted."""
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


def _stripes_of(runner, pages, length: int) -> np.ndarray:
    """The row's stripes out of the compressed-key array, and the means of
    its keys out of the pool: ([A, Nkv, n, D], the same)."""
    st, d = SPEC.sparse_stride, SPEC.head_dim
    n = length // st
    comp = np.asarray(runner.comp_keys[:, :, np.asarray(pages)], np.float32)
    comp = comp.reshape(*comp.shape[:2], -1, d)[:, :, :n]
    keys = np.asarray(runner.k_cache[:, :, np.asarray(pages)], np.float32)
    keys = keys.reshape(*keys.shape[:2], -1, d)[:, :, :n * st]
    return comp, keys.reshape(*keys.shape[:2], n, st, d).mean(axis=3)


def test_a_padded_batch_its_windows_and_the_compressed_keys():
    """Three prompts of 9, 41 and 60 tokens in one bucket of 64, then three
    windows over their slots with a dead slot between them: each row's
    logprobs are the reference's for its own tokens; the dead slot's state
    stays as it was BITWISE; every stripe of the compressed-key array is
    the mean of its two keys in the pool, across the borders of pages and
    of windows (a window of 4 tokens completes two stripes, one of them
    begun by the window before); the window counts its live rows and the
    keys they attended."""
    runner = ModelRunner(config(), params=PARAMS)
    assert runner.conv_state is None and runner.ssm_state.dtype == jnp.float32
    assert runner.comp_keys.shape == SPEC.comp_key_shape(128, PAGE)
    prompts = [prompt_of(n, 30 + n) for n in (9, 41, 60)]
    slots = [0, 1, 3]
    pages = [[1, 2], [3, 4, 5, 6], [7, 8, 9, 10, 11]]
    seqs = [PrefillSeq(tokens=np.asarray(p, np.int32), start_pos=0,
                       chunk_pages=np.asarray(pg[:4], np.int32),
                       hist_pages=None, sampling=(0.0, 0, 1.0))
            for p, pg in zip(prompts, pages)]
    runner.ssm_state = runner.ssm_state.at[:, 2].set(7.0)
    first = np.asarray(runner.prefill_batch(seqs, slots=slots)["tokens"])
    logits = np.asarray(runner.last_prefill_logits, np.float32)
    for row, prompt in enumerate(prompts):
        want = np.asarray(ref.logprobs_from(
            PARAMS, SPEC, prompt, len(prompt) - 1, ref.layer_of(SPEC)))[0]
        got = np.asarray(jax.nn.log_softmax(logits[row]))
        assert np.abs(got - want).max() < 0.05, (row, np.abs(got - want).max())
    rows = {s: (len(p), pg) for s, p, pg in zip(slots, prompts, pages)}
    toks, lps = [], []
    for w in range(3):
        t, lp, counted = _window(
            runner, {s: (pos + 4 * w, pg) for s, (pos, pg) in rows.items()},
            4)
        toks.append(t)
        lps.append(lp)
        assert float(np.asarray(counted["ssm"])[0]) == 12.0
        attended, context, read = np.asarray(counted["attn"])
        assert 0 < attended <= context
        # XLA's gather reads every slot's bucket (the widest row's 5 pages),
        # a layer and step.
        assert read == 4 * 3 * 4 * 5 * PAGE
    # The third window's keys in context over the three attention layers;
    # the rows past 48 keys (6 blocks of 8) attend fewer.
    assert context == 3 * sum(
        len(p) + 8 + m + 1 for p in prompts for m in range(4))
    # 6 of a row's 7 and 9 blocks: all but 8 and all but 24 of its keys.
    assert attended == 3 * sum(
        len(p) + 9 + m - dropped for p, dropped in zip(prompts, (0, 8, 24))
        for m in range(4))
    toks, lps = np.concatenate(toks), np.concatenate(lps)
    for row, (slot, prompt) in enumerate(zip(slots, prompts)):
        generated = [int(t) for t in toks[:, slot]]
        want = ref.reference_logprobs(PARAMS, SPEC,
                                      prompt + [int(first[row])], generated)
        assert near(lps[:, slot], want), (slot, distance(lps[:, slot], want))
    np.testing.assert_array_equal(_bits(runner.ssm_state[:, 2]),
                                  _bits(jnp.full_like(runner.ssm_state[:, 2],
                                                      7.0)))
    for (pos, pg) in rows.values():
        comp, means = _stripes_of(runner, pg, pos + 12)
        assert comp.shape[2] == (pos + 12) // 2 and np.abs(means).max() > 0.1
        assert np.abs(comp - means).max() < 0.02 * np.abs(means).max()
    memory = runner.memory_breakdown()
    assert memory["ssm_state_bytes"] == 4 * SPEC.ssm_state_bytes_per_row \
        == runner.ssm_state.nbytes


def test_a_frozen_row_and_an_inert_row_write_nothing():
    """A row at its cap from the window's second step, and a warm-up's
    inert row (slot -1): the frozen row's state after the window is its
    state after ONE step, bitwise, and the inert prefill leaves every
    slot's state and the compressed-key array's page 1 as they were."""
    runner = ModelRunner(config(), params=PARAMS)
    prompt = prompt_of(31, 4)
    seq = PrefillSeq(tokens=np.asarray(prompt, np.int32), start_pos=0,
                     chunk_pages=np.asarray([1, 2], np.int32),
                     hist_pages=None, sampling=(0.0, 0, 1.0))
    runner.prefill_batch([seq], slots=[1])
    before = np.asarray(runner.ssm_state)
    comp = np.asarray(runner.comp_keys, np.float32)
    inert = PrefillSeq(tokens=np.zeros(32, np.int32), start_pos=0,
                       chunk_pages=np.zeros(1, np.int32), hist_pages=None,
                       sampling=(0.0, 0, 1.0))
    runner.prefill_batch([inert] * 2, fetch=False)
    np.testing.assert_array_equal(_bits(runner.ssm_state), _bits(before))
    np.testing.assert_array_equal(
        np.asarray(runner.comp_keys, np.float32)[:, :, 1:], comp[:, :, 1:])
    # Two pages hold 32 tokens: position 31 is the last, the cap 32.
    _window(runner, {1: (31, [1, 2])}, 1)
    one = np.asarray(runner.ssm_state)
    assert np.abs(one[:, 1] - before[:, 1]).max() > 0
    runner2 = ModelRunner(config(), params=PARAMS)
    runner2.prefill_batch([seq], slots=[1])
    _window(runner2, {1: (31, [1, 2])}, 4)
    np.testing.assert_array_equal(_bits(runner2.ssm_state), _bits(one))


def test_a_group_over_the_bound_runs_in_parts():
    """Three prompts in a bucket of 64 under ``max_prefill_tokens`` 64 run a
    prompt at a time, 128 two at a time, 256 in one program: the same
    logits, tokens and states, and the programs a smaller group draws."""
    prompts = [prompt_of(n, 80 + n) for n in (40, 57, 64)]
    pages = [[1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 12]]
    seqs = [PrefillSeq(tokens=np.asarray(p, np.int32), start_pos=0,
                       chunk_pages=np.asarray(pg, np.int32),
                       hist_pages=None, sampling=(0.0, 0, 1.0))
            for p, pg in zip(prompts, pages)]
    got = {}
    for bound, programs in ((256, {(64, 4)}), (128, {(64, 2), (64, 1)}),
                            (64, {(64, 1)})):
        runner = ModelRunner(config(max_prefill_tokens=bound), params=PARAMS)
        out = runner.prefill_batch(seqs, slots=[0, 2, 3])
        assert {key[:2] for key in runner._prefill_cache} == programs
        got[bound] = [np.asarray(a, np.float32) for a in (
            out["tokens"][:3], runner.last_prefill_logits[:3],
            runner.ssm_state, runner.comp_keys)]
        assert got[bound][1].shape == (3, SPEC.vocab_size)
    for bound in (128, 64):
        for name, a, b in zip(("tokens", "logits", "state", "stripes"),
                              got[bound], got[256]):
            if name == "stripes":   # (page 0 is the padding rows' scratch)
                a, b = a[:, :, 1:], b[:, :, 1:]
            assert np.abs(a - b).max() <= 0.02 * np.abs(b).max(), (bound,
                                                                    name)
    np.testing.assert_array_equal(got[64][2][:, 1], 0.0)
    fetched = ModelRunner(config(max_prefill_tokens=64), params=PARAMS
                          ).prefill_batch(seqs)
    np.testing.assert_array_equal(fetched, got[256][0].astype(np.int64))


def _kernel_window_case():
    """The operands of hybrid.window_step over four slots of a pool of 60
    pages of 32 (whole lane tiles of a packed head: 32 x 16 / 128): rows
    300 and 77 tokens deep, a row at its cap (it holds 128 tokens and is
    not live) and a slot that holds nothing."""
    rows, window, pages, page = 4, 4, 60, 32
    nkv, d = SPEC.num_kv_heads, SPEC.head_dim
    key = jax.random.key(3)
    pool = (SPEC.pool_layers, nkv, pages, page, d)
    k_cache = jax.random.normal(key, pool, jnp.bfloat16)
    v_cache = jax.random.normal(jax.random.fold_in(key, 1), pool,
                                jnp.bfloat16)
    comp = hybrid.stripe_means(
        jnp.moveaxis(k_cache, 1, 3).reshape(-1, pages * page, nkv, d),
        SPEC.sparse_stride).reshape(SPEC.pool_layers, pages, -1, nkv, d)
    comp = jnp.moveaxis(comp, 3, 1)
    assert comp.shape == SPEC.comp_key_shape(pages, page)
    buf = jnp.zeros((SPEC.pool_layers, nkv, rows, window, d), jnp.bfloat16)
    s_shape, _ = SPEC.ssm_state_shapes
    state = (jax.random.normal(jax.random.fold_in(key, 2),
                               (SPEC.ssm_layers, rows, *s_shape)),)
    hist = jnp.asarray([300, 77, 128, 0])
    table = jnp.asarray(np.arange(1, 41).reshape(4, 10), jnp.int32)
    live = jnp.asarray([True, True, False, False])
    args = (PARAMS, SPEC, k_cache, v_cache, buf, buf, jnp.int32(0),
            jnp.asarray([3, 5, 7, 0]), table, hist, state, live)
    return args, dict(positions=hist, comp=comp)


def test_the_window_step_with_both_kernels_interpreted():
    """hybrid.window_step with the recurrence's kernel, the stripes' kernel
    and the pool's reader (over the chosen blocks' table) interpreted,
    against XLA's: the live rows' logits, the state and the counts agree."""
    args, kw = _kernel_window_case()
    live = np.asarray(args[-1])
    want = hybrid.window_step(*args, **kw)
    got = hybrid.window_step(*args, **kw,
                             backends=Backends(attention="pallas",
                                               ssm="kernel", interpret=True))
    np.testing.assert_allclose(np.asarray(got[0], np.float32)[live],
                               np.asarray(want[0], np.float32)[live],
                               atol=0.03 * float(jnp.abs(want[0]).max()))
    # (The readers round their sums apart, and the mixers behind the first
    # attention layer read what it gave.)
    np.testing.assert_allclose(np.asarray(got[3][0]), np.asarray(want[3][0]),
                               atol=0.03 * float(jnp.abs(want[3][0]).max()))
    np.testing.assert_array_equal(np.asarray(got[4]["attn"])[:, :2],
                                  np.asarray(want[4]["attn"])[:, :2])
    attended, context, gathered = np.asarray(want[4]["attn"]).sum(axis=0)
    # 38 and 10 blocks: 5 whole ones and the query's own partly filled one.
    assert context == 3 * (301 + 78)
    assert attended == 3 * (5 * 8 + 301 % 8 + 5 * 8 + 78 % 8)
    # The choice read every slot's bucket under the gather, the live rows'
    # keys under the kernel.
    assert gathered == 3 * 4 * 10 * 32
    assert np.asarray(got[4]["attn"])[:, 2].sum() == context


# -- the stripes' kernel ---------------------------------------------------------

@pytest.mark.parametrize("maxp", [6, 20], ids=["a bucket of 6 pages",
                                               "a bucket of 20 pages"])
def test_the_kernel_s_scores_are_the_gather_s(maxp, monkeypatch):
    """attention.stripe_scores_pallas, interpreted, against
    ``pool_stripes`` and its product at every stripe a row holds: rows of
    unequal depth in two page-table buckets (a table of one and a half
    chunks, padded, and one of five), a row that fills its table, rows of
    no tokens (a dead slot's length is handed in as 0) first, between and
    last, a row that ends on a chunk's border, on a page's and mid-page."""
    from dynamo_tpu.engine import attention
    monkeypatch.setattr(attention, "STRIPE_CHUNK_TOKENS", 4 * PAGE)
    st, d, nkv = SPEC.sparse_stride, SPEC.head_dim, SPEC.num_kv_heads
    lens = np.asarray([0, maxp * PAGE, 0, 4 * PAGE, 3 * PAGE, 38, 2, 0])
    rows = len(lens)
    key = jax.random.key(maxp)
    comp = jax.random.normal(key, SPEC.comp_key_shape(200, PAGE),
                             jnp.bfloat16)
    qg = jax.random.normal(jax.random.fold_in(key, 1),
                           (rows, nkv, SPEC.q_per_kv, d), jnp.bfloat16)
    table = jnp.asarray(np.random.default_rng(maxp).permutation(
        np.arange(1, 200))[:rows * maxp].reshape(rows, maxp), jnp.int32)
    for layer in (0, 2):
        want = np.asarray(jnp.einsum(
            "bngd,nbid->bngi", qg,
            hybrid.pool_stripes(comp, jnp.int32(layer), table, SPEC),
            preferred_element_type=jnp.float32))
        got = np.asarray(attention.stripe_scores_pallas(
            qg, comp, jnp.int32(layer), table, jnp.asarray(lens, jnp.int32),
            PAGE, interpret=True))
        assert got.shape == want.shape == (
            rows, nkv, SPEC.q_per_kv, maxp * PAGE // st)
        for row, n in enumerate(lens // st):
            np.testing.assert_allclose(got[row, :, :, :n],
                                       want[row, :, :, :n], rtol=1e-5,
                                       atol=1e-5 * np.abs(want).max())


def test_what_the_kernel_did_not_write_reaches_no_live_row(monkeypatch):
    """The scores' block of a dead slot, of a row at its cap and past a
    live row's last whole stripe is undefined: filled with NaN behind the
    kernel, the live rows' logits, their state and the counts are what they
    were, bit for bit (every reader of the scores selects, none
    multiplies)."""
    from dynamo_tpu.engine import attention
    args, kw = _kernel_window_case()
    record = Backends(attention="pallas", interpret=True)
    want = hybrid.window_step(*args, **kw, backends=record)
    scores = attention.stripe_scores_pallas
    poisoned = []

    def poison(qg, comp, layer, page_table, lens, page, **how):
        dots = scores(qg, comp, layer, page_table, lens, page, **how)
        at = jnp.arange(dots.shape[-1])[None, :]
        held = (at < lens[:, None] // SPEC.sparse_stride)[:, None, None, :]
        poisoned.append(dots.shape)
        return jnp.where(held, dots, jnp.nan)

    monkeypatch.setattr(attention, "stripe_scores_pallas", poison)
    got = hybrid.window_step(*args, **kw, backends=record)
    assert poisoned and set(poisoned) == {(4, 2, 2, 160)}
    live = np.asarray(args[-1])
    assert np.isfinite(np.asarray(got[0], np.float32)[live]).all()
    np.testing.assert_array_equal(_bits(got[0].astype(jnp.float32))[live],
                                  _bits(want[0].astype(jnp.float32))[live])
    np.testing.assert_array_equal(_bits(got[3][0])[:, live],
                                  _bits(want[3][0])[:, live])
    np.testing.assert_array_equal(np.asarray(got[4]["attn"]),
                                  np.asarray(want[4]["attn"]))


def test_a_window_served_with_the_kernel_is_the_gather_s():
    """A runner whose record names the Pallas reader (interpreted here: the
    stripes' kernel and the pool's reader) against XLA's: two prompts, a
    dead slot between them, three windows: the same tokens, the same keys
    attended and in context, and the choice READ the live rows' keys where
    the gather read every slot's bucket."""
    prompts = {0: prompt_of(41, 71), 2: prompt_of(60, 101)}
    pages = {0: [1, 2, 3, 4], 2: [7, 8, 9, 10, 11]}
    served = {}
    for backend in ("xla", "pallas"):
        runner = ModelRunner(config(attention_backend=backend),
                             params=PARAMS)
        assert runner.backends.index == backend
        assert runner._get_window(4, 8)._labels["index_backend"] == backend
        seqs = [PrefillSeq(tokens=np.asarray(prompts[s], np.int32),
                           start_pos=0,
                           chunk_pages=np.asarray(pages[s][:4], np.int32),
                           hist_pages=None, sampling=(0.0, 0, 1.0))
                for s in prompts]
        runner.prefill_batch(seqs, slots=list(prompts))
        toks, counts = [], []
        for w in range(3):
            t, _, counted = _window(
                runner, {s: (len(prompts[s]) + 4 * w, pages[s])
                         for s in prompts}, 4)
            toks.append(t)
            counts.append(np.asarray(counted["attn"]))
        served[backend] = (np.concatenate(toks), np.stack(counts))
    (want, gathered), (got, walked) = served["xla"], served["pallas"]
    np.testing.assert_array_equal(got[:, [0, 2]], want[:, [0, 2]])
    np.testing.assert_array_equal(walked[:, :2], gathered[:, :2])
    assert (walked[:, 2] == walked[:, 1]).all()
    assert (gathered[:, 2] == 4 * 3 * 4 * 5 * PAGE).all()


# -- the engine ------------------------------------------------------------------

PROMPT = prompt_of(200, 7)


@pytest.fixture(scope="module")
def served():
    """One prompt of 200 tokens and 48 tokens after it, served whole and in
    chunks of 64 over their history: {bound: (tokens, logprobs, status)}."""
    out = {}

    @async_test
    async def serve():
        for bound in (256, 64):
            engine = TPUEngine(config(max_prefill_tokens=bound),
                               params=PARAMS)
            engine.start()
            try:
                toks, lps = await collect(engine, PROMPT, 48)
                out[bound] = (toks, lps, engine.perf_status(),
                              engine.chunk_dispatch_count)
            finally:
                engine.stop()

    serve()
    return out


def test_the_engine_serves_what_the_reference_computes(served):
    """Prefill then decode through the cache against the reference's full
    forward pass, 248 tokens deep where a query keeps 6 of up to 31 blocks;
    counters and labels."""
    toks, lps, status, chunks = served[256]
    assert len(toks) == 48 and chunks == 0
    want = ref.reference_logprobs(PARAMS, SPEC, PROMPT, toks)
    assert near(lps, want), distance(lps, want)
    assert status["ssm"]["layers"] == 4 and status["ssm"]["row_steps"] >= 44
    assert status["ssm"]["state_dtype"] == "float32"
    assert (status["attn"]["topk_blocks"], status["attn"]["block"]) == (6, 8)
    assert 15 < status["attn"]["selected_pct"] < 25     # 41 to 48 of 200 to 248
    assert status["attn"]["kv_entry_bytes"] == 3 * 2 * (2 * 16 + 8) * 2
    labels = status["compiles"]["programs"]["decode_window"]["labels"]
    assert "off (recurrent state has no snapshot)" in np.atleast_1d(
        labels["prefix_reuse"])


def test_chunks_over_their_history_serve_the_same(served):
    toks, lps, _, chunks = served[64]
    assert chunks >= 3
    want = ref.reference_logprobs(PARAMS, SPEC, PROMPT, toks)
    assert near(lps, want), distance(lps, want)
    assert toks[:8] == served[256][0][:8]


@pytest.mark.parametrize("switch", [
    dict(select="false"), dict(decay=1), dict(rope="false"),
    dict(qk_norm="false"), dict(gate="false"), dict(scale_depth=1),
    dict(precision="float8_e4m3fn")], ids=lambda s: "=".join(
        map(str, next(iter(s.items())))))
def test_each_control_fails_the_tolerance(served, switch):
    """What was served stands outside the tolerance from the reference with
    ONE equation switched: a skipped choice, a sum without forgetting, no
    rotation, no norm of q and k, no gate, another residual scale, float8
    activations."""
    toks, lps, _, _ = served[256]
    wrong = ref.control_logprobs(PARAMS, SPEC, PROMPT, toks, **switch)
    assert not near(lps, wrong), (switch, distance(lps, wrong))


def test_a_state_in_bfloat16_is_told_by_its_type():
    """The check cannot tell a bfloat16 state at this size (its distance
    from what is served is the float32 reference's own), so the arrays'
    type is asserted where they are made."""
    runner = ModelRunner(config(), params=PARAMS)
    assert runner.ssm_state.dtype == jnp.float32
    assert runner.ssm_state.nbytes == 4 * 4 * 4 * 4 * 16 * 16
    assert runner.backends.labels("decode_window")["ssm_state"] == "float32"


@pytest.mark.parametrize("prompt,decoded", [(256, 64), (96, 512)])
def test_a_state_in_bfloat16_is_told_by_the_state_it_leaves(prompt, decoded):
    """What the logprobs cannot tell (a bfloat16 state reads 0.0035 nat
    where what is served reads 0.0025, on the chip at 6,000 tokens: the
    slow heads forget over 256 tokens, so the rounding never piles up past
    the bfloat16 activations'), the STATE tells: after a prompt's chunks
    and ``decoded`` single steps the program's state is the float64
    recurrence's over the same q, k, v to 7e-4 of its largest entry (the
    chunks' decayed products; 7e-5 where most tokens are single steps),
    and the same recurrence with its state rounded to bfloat16 after every
    token (the reference's ``state=bfloat16``) is 2.3e-2 to 2.9e-2 off:
    the limit lies between, four times over the one and seven under the
    other."""
    tokens = prompt + decoded
    lp, h, _ = _lightning_inputs(1, tokens, 11)
    state = jnp.zeros((1, *SPEC.ssm_state_shapes[0]), jnp.float32)
    positions = jnp.arange(tokens)[None, :]
    parts = hybrid._lightning_project(h, lp, SPEC, positions)
    ahead = tuple(a[:, :prompt] for a in parts)
    _, state = hybrid.lightning_recurrence(
        ahead, SPEC, state, jnp.ones((1, prompt), bool))
    for t in range(prompt, tokens):
        _, state = hybrid.lightning_step(h[:, t], lp, SPEC, state,
                                         positions[:, t], jnp.ones(1, bool))
    q, k, v, _ = (np.asarray(a, np.float64) for a in parts)
    lam = np.asarray(hybrid.lightning_decay(SPEC), np.float64)[:, None, None]

    def recurrence(kept):
        s = np.zeros(state.shape[1:], np.float64)
        for t in range(tokens):
            s = kept(lam * s + v[0, t, :, :, None] * k[0, t, :, None, :])
        return s

    def bf16(a):
        return np.asarray(jnp.asarray(a, jnp.float32).astype(jnp.bfloat16),
                          np.float64)

    want = recurrence(lambda a: a)
    scale = np.abs(want).max()
    served = np.abs(np.asarray(state[0], np.float64) - want).max() / scale
    rounded = np.abs(recurrence(bf16) - want).max() / scale
    assert served < 3e-3 < 2e-2 < rounded, (served, rounded)
