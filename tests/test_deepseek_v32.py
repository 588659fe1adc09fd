"""The DeepSeek-V3.2 block (latent attention over a paged cache of latent
entries and index keys, a learned indexer that keeps ``index_topk`` keys a
query, a leading dense layer, a grouped sigmoid router with a selection bias
and a scaling factor of which this device holds a share, YaRN on part of a
head) on the normal path, at toy size on the CPU: the program's reader on the
catalog row's keys and its refusals; prefill, chunk prefill over history, the
decode window and the single decode step through the paged latent cache
against the plain reference's full forward
(benchmark/references/deepseek_v32.py), on logits, with a toy ``index_topk``
(40) SMALLER than the context (72) so that selection is in force in the
with-history chunk (48 keys), the window and the single step; the absorbed
form against the expanded; the served choice's SET against ``lax.top_k``'s;
the grouped router against a written-out loop; the shares' sum; YaRN against
its formula; prefix reuse, preemption and the engine's counters. Nothing here
is a device number.
"""
import asyncio
import dataclasses
import functools
import json
import math
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import async_test
from test_smallthinker import distance

from benchmark.references import deepseek_v32 as ref
from dynamo_tpu.engine import model
from dynamo_tpu.engine.backends import XLA, Backends, choose, pallas_refusal
from dynamo_tpu.engine.config import (DeepseekV32Spec, EngineConfig,
                                      ModelSpec, UnsupportedBlockError,
                                      block_refusals, pool_access)
from dynamo_tpu.engine.kv_quant import scatter_tokens
from dynamo_tpu.engine.quant import QUANT_LAYER_KEYS, quantize_params
from dynamo_tpu.engine.runner import ModelRunner, _prefill_with_history

# The catalog row's ``config`` (model-configs guide, architectures.jsonl:
# DeepSeek-V3.2-Exp), verbatim.
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "hidden_act": "silu", "hidden_size": 7168, "index_head_dim": 128,
    "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v32", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 4, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 129280}
#: The model without its draft module: what the reader takes whole.
MODEL = {**CATALOG, "num_nextn_predict_layers": 0}
#: The cell's cut: 16 chips share each layer, this one holds share 0.
CUT = {**MODEL, "num_hidden_layers": 9, "first_k_dense_replace": 1,
       "n_routed_experts": 16, "vocab_size": 16160,
       "expert_parallel": {"routed_experts": 256, "first_expert": 0,
                           "chips_per_layer": 16}}
#: One dense layer and two expert layers; 16 experts routed in 4 groups of
#: which the SECOND quarter is held (a share that does not start at 0); 16
#: index heads, so that no two keys' scores are exactly 0 together (with 4,
#: one key in 16 has every head's product under the relu, and ties at rank
#: ``index_topk`` are all kept where ``lax.top_k`` keeps the lower indices).
TOY = {**MODEL, "hidden_size": 64, "intermediate_size": 96,
       "num_hidden_layers": 3, "first_k_dense_replace": 1,
       "num_attention_heads": 4, "num_key_value_heads": 4,
       "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
       "qk_rope_head_dim": 8, "v_head_dim": 16, "index_n_heads": 16,
       "index_head_dim": 16, "index_topk": 40, "moe_intermediate_size": 32,
       "n_routed_experts": 4, "n_group": 4, "topk_group": 2,
       "num_experts_per_tok": 3,
       "expert_parallel": {"routed_experts": 16, "first_expert": 4},
       "max_position_embeddings": 2048, "vocab_size": 512}
PAGE, SEQ, FIRST, CHUNK, WINDOW = 4, 72, 24, 24, 4


def read_spec(cfg: dict) -> ModelSpec:
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "config.json")
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(cfg, fh)
        return ModelSpec.from_hf_config(path)


def count(shapes) -> int:
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple)))


def test_from_hf_config_reads_the_catalog_rows_keys_verbatim():
    spec = read_spec(MODEL)
    assert isinstance(spec, DeepseekV32Spec) and spec.latent
    assert (spec.hidden_size, spec.num_layers, spec.num_heads,
            spec.head_dim, spec.intermediate_size) == (7168, 61, 128, 192,
                                                       18432)
    assert (spec.kv_lora_rank, spec.q_lora_rank, spec.qk_nope_head_dim,
            spec.qk_rope_head_dim, spec.v_head_dim) == (512, 1536, 128, 64,
                                                        128)
    assert (spec.index_n_heads, spec.index_head_dim,
            spec.index_topk) == (64, 128, 2048)
    assert (spec.num_experts, spec.router_width, spec.first_expert,
            spec.num_experts_per_tok, spec.num_shared_experts,
            spec.expert_size) == (256, 256, 0, 8, 1, 2048)
    assert (spec.n_group, spec.topk_group, spec.routed_scaling_factor,
            spec.first_k_dense) == (8, 4, 2.5, 3)
    assert spec.moe_router == "sigmoid_topk" and spec.norm_topk_prob
    assert spec.moe_select_bias and not spec.parallel_block
    assert spec.norm_kind == "rms" and spec.rms_norm_eps == 1e-6
    assert spec.rope_yarn == (40.0, 4096, 32.0, 1.0, 1.0)
    assert not spec.tie_word_embeddings and not spec.has_layer_pattern
    # 192^-0.5 * (0.1 ln 40 + 1)^2.
    assert abs(spec.attn_scale - 192 ** -0.5 * 1.3688879 ** 2) < 1e-6
    assert 670e9 < spec.num_params() < 673e9        # "671B-A37B", no draft
    # What a token leaves in a layer: 512 + 64 (+ 64 of padding) and 128.
    assert spec.kv_entry == (1, (640, 128))


def test_the_cut_holds_16_of_256_experts_and_8_44_gb():
    cut = dataclasses.replace(read_spec(CUT), quant="int8")
    assert (cut.num_experts, cut.router_width, cut.first_expert,
            cut.first_k_dense) == (16, 256, 0, 1)
    shapes = model.param_shapes(cut)["layers"]
    assert shapes["moe_gate"] == (8, 7168, 256)
    assert shapes["moe_bias"] == (8, 256, 1)
    assert shapes["moe_w_down"] == (8, 16, 2048, 7168)
    assert shapes["shared_w_gate"] == (8, 1, 7168, 2048)
    assert shapes["dense_w_gate"] == (1, 7168, 18432)
    assert shapes["wk_b"] == shapes["wv_b"] == (8, 512, 16384)
    assert shapes["dense_wkv_a"] == (1, 7168, 576)
    assert "dense_moe_gate" not in shapes and "w_gate" not in shapes
    # The issue's reckoning: attention 187.11 M, indexer 13.96 M.
    attention = (7168 * 1536 + 1536 * 24576 + 7168 * 576 + 512 * 32768
                 + 16384 * 7168)
    indexer = 1536 * 8192 + 7168 * 128 + 7168 * 64
    assert round(attention / 1e6, 2) == 187.11
    assert round(indexer / 1e6, 2) == 13.96
    small = 2 * 7168 + 1536 + 512 + 2 * 128     # norms, the indexer's bias
    expert = 3 * 7168 * 2048
    layer = attention + indexer + small + 17 * expert + 7168 * 256 + 256
    dense = attention + indexer + small + 3 * 7168 * 18432
    assert cut.num_params() == (8 * layer + dense + 2 * 16160 * 7168 + 7168)
    assert 8.43e9 < cut.num_params() < 8.45e9
    assert 10.2 < cut.weight_read_step_ms(819.0) < 10.4
    assert set(QUANT_LAYER_KEYS) >= {"wq_a", "wk_b", "index_wk",
                                     "dense_w_down", "dense_wq_b"}
    assert "index_w" not in QUANT_LAYER_KEYS
    config = EngineConfig(model=cut)
    # 9 layers x (640 + 128) values x 2 bytes; the issue's 12,672 without
    # the 64 lanes of padding a layer.
    assert config.kv_token_bytes() == 9 * 768 * 2 == 13824
    assert config.resolve_page_size("tpu") == 64
    assert config.resolve_page_size("cpu") == 16
    assert pool_access("auto", "tpu", 1, 192, None, True) == ("pallas",
                                                              "in_place")
    assert pool_access("auto", "cpu", 1, 192, None, True) == ("xla",
                                                              "scatter")
    assert config.resolve_decode_window(
        __import__("dynamo_tpu.engine.config", fromlist=["x"])
        .DEVICE_PEAKS["TPU v5 lite"]) == 8


@pytest.mark.parametrize("cfg", [TOY, CUT, MODEL],
                         ids=["toy", "cut", "published"])
def test_num_params_is_the_sum_of_param_shapes(cfg):
    spec = read_spec(cfg)
    assert spec.num_params() == count(model.param_shapes(spec))
    shapes, specs = model.param_shapes(spec), model.param_specs(spec)
    assert set(shapes["layers"]) == set(specs["layers"])
    int8 = model.param_specs(dataclasses.replace(spec, quant="int8"))
    assert set(int8["layers"]) == set(shapes["layers"])


@pytest.mark.parametrize("key, value, says", [
    ("num_nextn_predict_layers", 1, "ROADMAP R10"),
    ("scoring_func", "softmax", "sigmoid"),
    ("topk_method", "greedy", "grouped"),
    ("moe_layer_freq", 2, "expert layer"),
    ("hidden_act", "gelu", "SwiGLU"),
    ("attention_bias", True, "bias"),
    ("q_lora_rank", None, "low-rank query"),
    ("n_shared_experts", 2, "averaged"),
    ("num_key_value_heads", 8, "every head"),
    ("rope_scaling", {"type": "linear", "factor": 4}, "YaRN"),
    ("rope_scaling", {**CATALOG["rope_scaling"], "mscale": 0.7},
     "mscale")])
def test_the_reader_refuses_what_it_cannot_express(key, value, says):
    with pytest.raises(UnsupportedBlockError, match=says):
        read_spec({**MODEL, key: value})


COHERE = {"model_type": "cohere2_moe", "layer_types": ["full_attention"] * 2,
          "num_hidden_layers": 2, "hidden_size": 64, "intermediate_size": 32,
          "num_attention_heads": 4, "num_experts": 4,
          "num_experts_per_tok": 2, "vocab_size": 128}


@pytest.mark.parametrize("key, value, says", [
    ("first_k_dense_replace", 1, "prefix_dense_intermediate_size"),
    ("n_group", 2, "groups"), ("routed_scaling_factor", 2.5, "groups"),
    ("topk_method", "noaux_tc", "selection bias")])
def test_cohere2_moe_with_a_prefix_or_a_grouped_router_is_still_refused(
        key, value, says):
    """The program now serves a dense prefix and a grouped, biased, scaled
    router (this block's); how the Cohere2-MoE family would state either is
    not written down, so its reader still refuses them, with that reason."""
    assert read_spec(COHERE).num_experts == 4
    with pytest.raises(UnsupportedBlockError, match=says):
        read_spec({**COHERE, key: value})


REFUSED = {
    "int8 KV pages": dict(quant_kv="int8"),
    "host and disk KV tiers": dict(host_cache_pages=8),
    "speculative decoding": dict(spec_decode="ngram"),
    "ring and sequence-parallel": dict(ring_attention=True),
    "pipeline of layer stages": dict(pp_microbatch=True),
    "LoRA adapters": dict(max_adapters=2),
    "tp/pp/dp/sp mesh": dict(tp=2),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_every_path_that_assumes_a_k_and_v_pair_is_refused_by_name(what):
    spec = read_spec(TOY)
    assert block_refusals(spec, EngineConfig(model=spec, page_size=4)) == []
    config = EngineConfig(model=spec, page_size=4, **REFUSED[what])
    said = [str(r) for r in block_refusals(spec, config)]
    assert any(what in s for s in said), said
    with pytest.raises(UnsupportedBlockError):
        ModelRunner(config)


def test_parcels_checkpoints_and_embeddings_are_refused():
    spec = read_spec(TOY)
    assert any("KV parcel" in str(r)
               for r in block_refusals(spec, kv_transfer=True))
    assert block_refusals(read_spec(COHERE), kv_transfer=True) == []
    assert any("safetensors" in str(r)
               for r in block_refusals(spec, checkpoint=True))
    assert any("mm_embeds" in str(r)
               for r in block_refusals(spec, embeddings=True))
    runner = ModelRunner(EngineConfig(model=spec, page_size=4, num_pages=16))
    with pytest.raises(UnsupportedBlockError, match="KV parcel"):
        runner.extract_pages([1])
    with pytest.raises(UnsupportedBlockError, match="KV parcel"):
        runner.insert_pages(np.zeros((2, 3, 1, 1, 4, 16), np.float32), [1])


@pytest.mark.parametrize("backend, platform, mesh, quant_kv, want", [
    ("auto", "tpu", 1, None, ("pallas", "in_place")),
    ("auto", "cpu", 1, None, ("xla", "scatter")),
    ("auto", "tpu", 4, None, ("xla", "scatter")),
    ("auto", "tpu", 1, "int8", ("xla", "scatter")),
    ("xla", "tpu", 1, None, ("xla", "in_place")),
    ("pallas", "tpu", 1, None, ("pallas", "in_place")),
    ("pallas", "cpu", 1, None, ("pallas", "scatter")),    # interpreted
    ("pallas", "tpu", 4, None, ("pallas", "scatter"))])   # the runner's to refuse
def test_who_reads_and_writes_a_latent_pool(backend, platform, mesh,
                                            quant_kv, want):
    """config.pool_access for a latent pool: under "auto" the kernel on one
    TPU device over bfloat16 entries and XLA's walk everywhere else; the
    window commits in place on one TPU device under EITHER reader (both read
    the row-major pool as it lies); a requested reader comes back as asked.
    The choice reads what a token leaves in the pool and the device, never
    the heads' own width."""
    for head_dim in (192, 128):
        assert pool_access(backend, platform, mesh, head_dim, quant_kv,
                           latent=True) == want


def _bare_config(**config):
    from types import SimpleNamespace
    return SimpleNamespace(**{**dict(
        page_size=4, max_pages_per_seq=128, attention_backend="auto",
        spec_decode=None), **config})


def _chosen(spec, mesh=1, quant_kv=None, platform="cpu", **config):
    """backends.choose for a runner of ``spec`` that observes this."""
    return choose(_bare_config(**config), spec, platform, mesh, quant_kv)


def test_the_runner_takes_the_kernel_for_a_latent_pool_where_it_serves():
    """pallas_refusal has no sentence left for a latent pool on one device
    (whatever the heads' width: 24 at the toy, 192 as published), and keeps
    one each for a mesh and for int8 pages; a requested "pallas" runs
    interpreted on the CPU, one reader for the decode step and the window;
    "auto" on a TPU is the kernel and on the CPU XLA's walk (no reader)."""
    from dynamo_tpu.engine.attention import (latent_history_pallas,
                                             latent_index_pallas)
    spec = read_spec(TOY)
    assert pallas_refusal(spec, 4, 1, None) is None
    assert "one device" in pallas_refusal(spec, 4, 2, None)
    assert "int8 latent pages" in pallas_refusal(spec, 4, 1, "int8")
    # A K-and-V pool of the same head width is still refused by its width.
    kv = ModelSpec(head_dim=spec.head_dim, num_heads=4, num_kv_heads=4,
                   hidden_size=96)
    assert spec.head_dim == 24
    assert "head_dim" in pallas_refusal(kv, 4, 1, None)
    runner = ModelRunner(EngineConfig(model=spec, page_size=4, num_pages=16,
                                      attention_backend="pallas"))
    assert runner.attention_backend == "pallas"
    assert runner.backends.kv_commit == "scatter"    # the CPU
    # The reader of the entries and the indexer over the index keys come
    # together, one pair for the decode step and the window: whoever walks
    # the one walks the other.
    reader, indexer = runner.backends.latent_readers()
    assert reader.func is latent_history_pallas
    assert indexer.func is latent_index_pallas
    assert runner.backends.index == "pallas"
    # ... and one kernel each for every page-table bucket: the table's limit.
    assert reader.keywords == indexer.keywords == {
        "interpret": True, "table": runner.config.max_pages_per_seq}
    on_tpu = _chosen(spec, platform="tpu")
    for bound in on_tpu.latent_readers():
        assert bound.keywords == {"interpret": False, "table": 128}
    assert (on_tpu.attention, on_tpu.index, on_tpu.kv_commit) == (
        "pallas", "pallas", "in_place")
    for elsewhere in (_chosen(spec), _chosen(spec, mesh=4, platform="tpu")):
        assert elsewhere.latent_readers() == (None, None)
        assert (elsewhere.attention, elsewhere.index,
                elsewhere.kv_commit) == ("xla", "xla", "scatter")
    # A block without an indexer has no such label.
    kv = _chosen(ModelSpec(head_dim=128, num_heads=4, num_kv_heads=4,
                           hidden_size=512), platform="tpu")
    assert (kv.attention, kv.index) == ("pallas", None)


def test_a_bucket_that_xla_gathers_whole_grows_by_1024_tokens():
    """An XLA gather reads the whole bucket of every slot, so past 2,048
    tokens such a bucket is a multiple of 1,024 tokens where a kernel's,
    which walks live pages alone, stays a power of two. A latent pool
    follows its reader like any other since the indexer's scores are the
    kernel's too (PR 37; it kept the steps under either reader while XLA
    gathered its index keys over the bucket)."""
    from dynamo_tpu.engine.config import window_page_bucket
    needs = (1, 9, 17, 33, 49, 65, 81, 100, 121, 500)
    steps = [8, 16, 32, 48, 64, 80, 96, 112, 128, 128]
    powers = [8, 16, 32, 64, 64, 128, 128, 128, 128, 128]
    assert [window_page_bucket(n, "xla", 64, 128) for n in needs] == steps
    assert [window_page_bucket(n, "pallas", 64, 128) for n in needs] == powers
    # A page of 16: steps of 64 pages past 128.
    assert [window_page_bucket(n, "xla", 16, 512)
            for n in (100, 129, 193, 400)] == [128, 192, 256, 448]
    spec = read_spec(TOY)
    for platform, want in (("tpu", powers), ("cpu", steps)):
        runner = object.__new__(ModelRunner)
        runner.config = _bare_config(page_size=64)
        runner.attention_backend = choose(runner.config, spec, platform, 1,
                                          None).attention
        assert [runner.bucket_pages_for(n) for n in needs] == want


# -- the pieces ----------------------------------------------------------------

@pytest.mark.parametrize("position", [1, 777, 7000])
def test_yarn_against_the_formula(position):
    """f'_i at three positions: lo = floor(d(32)) = 10, hi = ceil(d(1)) =
    23 at the published sizes; plain below lo, a fortieth above hi."""
    spec = read_spec(MODEL)
    cos, sin = model.spec_rope_tables(spec, jnp.asarray([position]))
    assert cos.shape == (1, 32)
    want = []
    for i in range(32):
        f = 10000.0 ** (-2 * i / 64)

        def d(r):
            return 64 * math.log(4096 / (2 * math.pi * r)) \
                / (2 * math.log(10000.0))

        lo, hi = math.floor(d(32)), math.ceil(d(1))
        assert (lo, hi) == (10, 23)
        ramp = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        want.append(f * (1 - ramp) + f / 40 * ramp)
    ang = position * np.asarray(want)
    np.testing.assert_allclose(np.asarray(cos[0]), np.cos(ang), atol=2e-4)
    np.testing.assert_allclose(np.asarray(sin[0]), np.sin(ang), atol=2e-4)
    np.testing.assert_allclose(ref.frequencies(64, 10000.0, spec.rope_yarn),
                               want, rtol=1e-6)
    plain, _ = model.rope_tables(jnp.asarray([position]), 64, 10000.0)
    assert np.abs(np.asarray(plain - cos)).max() > 1e-3 or position == 1


def written_out_route(score, bias, n_group, topk_group, k, factor):
    """The grouped choice as a loop over rows, groups and experts."""
    gates, picks = [], []
    for s_row, z_row in zip(score, score + bias):
        per = len(z_row) // n_group
        group_score = []
        for g in range(n_group):
            members = sorted(z_row[g * per:(g + 1) * per], reverse=True)
            group_score.append(members[0] + members[1])
        kept = sorted(range(n_group), key=lambda g: -group_score[g])
        kept = kept[:topk_group]
        allowed = [e for e in range(len(z_row)) if e // per in kept]
        chosen = sorted(allowed, key=lambda e: -z_row[e])[:k]
        total = sum(s_row[e] for e in chosen)
        picks.append(chosen)
        gates.append([s_row[e] / total * factor for e in chosen])
    return np.asarray(gates), np.asarray(picks)


def test_the_grouped_router_against_a_written_out_loop():
    spec = read_spec({**TOY, "n_routed_experts": 16,
                      "expert_parallel": None})
    rng = np.random.default_rng(4)
    logits = rng.standard_normal((64, 16)).astype(np.float32) * 2
    bias = (rng.standard_normal(16) * 0.25).astype(np.float32)
    gates, picks = model.moe_route(jnp.asarray(logits), spec,
                                   jnp.asarray(bias))
    score = 1 / (1 + np.exp(-logits.astype(np.float64)))
    want_g, want_p = written_out_route(score, bias.astype(np.float64), 4, 2,
                                       3, 2.5)
    assert (np.sort(np.asarray(picks), -1) == np.sort(want_p, -1)).all()
    order, want_order = np.argsort(picks, -1), np.argsort(want_p, -1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(gates), order, -1),
        np.take_along_axis(want_g, want_order, -1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 2.5, rtol=1e-5)
    # The bias decides the choice and never enters a gate; groups matter.
    _, unbiased = model.moe_route(jnp.asarray(logits), spec,
                                  jnp.zeros(16, jnp.float32))
    assert (np.sort(np.asarray(unbiased), -1) != np.sort(want_p, -1)).any()
    flat = dataclasses.replace(spec, n_group=1, topk_group=1)
    _, ungrouped = model.moe_route(jnp.asarray(logits), flat,
                                   jnp.asarray(bias))
    assert (np.sort(np.asarray(ungrouped), -1) != np.sort(want_p, -1)).any()


@pytest.mark.parametrize("k, rows, width", [(12, 5, 40), (2048, 3, 5000),
                                            (7, 4, 7), (9, 2, 6)])
def test_the_served_choice_is_lax_top_k_s_set(k, rows, width):
    """``select_topk`` keeps exactly the entries ``lax.top_k`` returns over
    the same float32 scores, short rows included; where scores TIE across
    rank k it keeps them all, ``top_k`` the lower indices."""
    rng = np.random.default_rng(k)
    scores = rng.standard_normal((rows, width)).astype(np.float32)
    valid = np.arange(width)[None] < rng.integers(1, width + 1, (rows, 1))
    valid[0] = True

    def both(scores):
        got = np.asarray(model.select_topk(jnp.asarray(scores),
                                           jnp.asarray(valid), k))
        masked = jnp.where(jnp.asarray(valid), jnp.asarray(scores), -jnp.inf)
        _, idx = jax.lax.top_k(masked, min(k, width))
        want = np.zeros_like(valid)
        np.put_along_axis(want, np.asarray(idx), True, axis=-1)
        return got, want & valid

    got, want = both(scores)
    assert (got == want).all()
    assert (got.sum(-1) == np.minimum(valid.sum(-1), k)).all()
    scores[0, : width // 2 + 1] = 0.25              # a run of exact ties
    got, want = both(scores)
    assert (got[1:] == want[1:]).all() and (got[0] >= want[0]).all()
    tied = scores[0] == 0.25
    assert (got[0] == want[0]).all() or got[0][tied].all()


# -- the program against the reference, on logits -------------------------------

@functools.cache
def toy(quant: str | None, seed: int = 3, cfg: str = "TOY"):
    spec = dataclasses.replace(read_spec(globals()[cfg]), quant=quant)
    params = model.init_params(spec, jax.random.key(seed))
    layers = params["layers"]
    # A router and an indexer whose choices are decided, so that bfloat16
    # against float32 flips few experts and keys at this toy width.
    layers["moe_gate"] = layers["moe_gate"] * 8.0
    for name in ("index_wq_b", "dense_index_wq_b"):
        layers[name] = layers[name] * 4.0
    if quant:
        params = jax.tree.map(jnp.asarray, quantize_params(
            jax.tree.map(np.asarray, params)))
    tokens = np.asarray(jax.random.randint(
        jax.random.key(seed + 1), (2, SEQ), 1, spec.vocab_size), np.int32)
    return spec, params, tokens


def pools(spec, pages: int):
    heads, (dk, dv) = spec.kv_entry
    shape = (spec.num_layers, heads, pages, PAGE)
    return (jnp.zeros((*shape, dk), jnp.bfloat16),
            jnp.zeros((*shape, dv), jnp.bfloat16))


def served_logits(spec, params, tokens, record=XLA
                  ) -> tuple[np.ndarray, np.ndarray]:
    """Logits the program gives after positions FIRST-1 (whole-prompt
    prefill), FIRST+CHUNK-1 (chunk prefill over cached history), then one
    row a decoded position: WINDOW steps of the window program with its
    commit, the rest by the single decode step; [B, rows, V]. And the
    window's counts [L, 2] of its last step. ``record``: who reads the
    entries in the window and the step (XLA: XLA's walk)."""
    b = tokens.shape[0]
    pages = SEQ // PAGE
    k, v = pools(spec, b * pages + 1)
    table = (1 + np.arange(b * pages, dtype=np.int32)).reshape(b, pages)
    rows = []
    pos = np.broadcast_to(np.arange(FIRST, dtype=np.int32), (b, FIRST))
    lens = np.full((b,), FIRST, np.int32)
    logits, k, v = jax.jit(lambda p, k, v: model.prefill_forward(
        p, spec, k, v, tokens[:, :FIRST], pos, table[:, :FIRST // PAGE],
        lens))(params, k, v)
    rows.append(logits)
    done = FIRST
    logits, k, v = jax.jit(lambda p, k, v: _prefill_with_history(
        p, spec, k, v, tokens[:, done:done + CHUNK], pos + done,
        table[:, done // PAGE:(done + CHUNK) // PAGE],
        np.full((b,), CHUNK, np.int32), table[:, :done // PAGE],
        np.full((b,), done, np.int32), record))(params, k, v)
    rows.append(logits)
    done += CHUNK

    def window(p, k, v):
        L = spec.num_layers
        heads, (dk, dv) = spec.kv_entry
        kbuf = jnp.zeros((L, heads, b, WINDOW, dk), k.dtype)
        vbuf = jnp.zeros((L, heads, b, WINDOW, dv), v.dtype)
        hist = jnp.full((b,), done, jnp.int32)
        out = []
        for m in range(WINDOW):
            logits, k_new, v_new, counted = model.decode_window_step(
                p, spec, k, v, kbuf, vbuf, jnp.int32(m),
                tokens[:, done + m], hist + m, table, hist,
                backends=record, live=jnp.ones((b,), bool))
            counts, stats = counted["attn"], counted["moe"]
            kbuf = kbuf.at[:, :, :, m].set(k_new.transpose(0, 2, 1, 3))
            vbuf = vbuf.at[:, :, :, m].set(v_new.transpose(0, 2, 1, 3))
            out.append(logits)
        at = done + np.arange(WINDOW)
        dest = jnp.asarray(table[:, at // PAGE].T)          # [M, B]
        off = jnp.broadcast_to(jnp.asarray(at % PAGE)[:, None], dest.shape)
        k = scatter_tokens(k, kbuf.transpose(0, 1, 3, 2, 4), dest, off)
        v = scatter_tokens(v, vbuf.transpose(0, 1, 3, 2, 4), dest, off)
        return jnp.stack(out), k, v, counts, stats

    logits, k, v, counts, stats = jax.jit(window)(params, k, v)
    rows += list(logits)
    done += WINDOW
    # The expert layers alone report a load; every layer counts its keys.
    assert stats.shape == (spec.num_layers - spec.first_k_dense, 5)
    assert (np.asarray(stats)[:, 4] == b * spec.num_experts_per_tok).all()
    decode = jax.jit(lambda p, k, v, t, at: model.decode_forward(
        p, spec, k, v, t, at, table, at + 1, backends=record))
    while done < SEQ:
        logits, k, v = decode(params, k, v, tokens[:, done],
                              np.full((b,), done, np.int32))
        rows.append(logits)
        done += 1
    return (np.asarray(jnp.stack(rows, axis=1), np.float32),
            np.asarray(counts))


def reference_logits(spec, params, tokens, **switches) -> np.ndarray:
    """The plain float32 forward's logits at the same positions."""
    layer = ref.layer_of(spec, **switches)
    at = [FIRST - 1, FIRST + CHUNK - 1, *range(FIRST + CHUNK, SEQ)]
    out = []
    with jax.default_matmul_precision("highest"):
        for row in tokens:
            x = ref.hidden_states(params, spec, row, layer)
            out.append(ref.logits_at(params, spec, x[jnp.asarray(at)]))
    return np.asarray(jnp.stack(out), np.float32)


CONTROLS = {"every key attended": {"select": False},
            "no rotation in the indexer": {"index_rope": False},
            "plain top-k for the grouped choice": {"groups": False},
            "no selection bias": {"bias": False},
            "no routed scaling factor": {"scaling": "1"},
            "plain frequencies for YaRN's": {"yarn": False},
            "a scale without mscale": {"scale": "24"},  # (16 + 8)^-0.5
            "the latent not normalised": {"kv_norm": False}}
#: Between what the program reads (0.060 bf16, 0.063 int8; 0.022 with every
#: key kept: at 40 keys of at most 72 a key that bfloat16 index scores put on
#: the other side of rank 40 than float32 ones carries a fortieth of a
#: query's attention, where at 2,048 it carries a two-thousandth) and what
#: the controls read (0.28 to 0.91; float8 0.46): nats of a toy on the CPU,
#: no device number.
TOLERANCE = 0.15


@pytest.mark.parametrize("reader", ["xla", "pallas"])
@pytest.mark.parametrize("quant", [None, "int8"])
def test_prefill_then_decode_agrees_with_the_reference_with_selection_in_force(
        quant, reader):
    """Under either side of config.pool_access: XLA's walk and XLA's
    indexer; the Pallas kernels (interpreted here), the indexer's scores
    under the same choice."""
    spec, params, tokens = toy(quant)
    assert FIRST < spec.index_topk == 40 < FIRST + CHUNK
    assert spec.first_k_dense == 1
    record = XLA if reader == "xla" else Backends(attention="pallas",
                                                  interpret=True)
    served, counts = served_logits(spec, params, tokens, record)
    full = reference_logits(spec, params, tokens)
    assert served.shape == full.shape == (2, 2 + SEQ - FIRST - CHUNK,
                                          spec.vocab_size)
    assert distance(served, full) < TOLERANCE
    # The window's last step: 2 rows of 52 keys in context, 40 attended.
    assert counts.shape == (spec.num_layers, 2)
    last = FIRST + CHUNK + WINDOW
    assert (counts[:, 0] == 2 * 40).all() and (counts[:, 1] == 2 * last).all()


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_each_control_fails_the_same_tolerance(control):
    spec, params, tokens = toy("int8")
    served, _ = served_logits(spec, params, tokens)
    wrong = reference_logits(spec, params, tokens, **CONTROLS[control])
    assert distance(served, wrong) > TOLERANCE, control


@pytest.mark.parametrize("skip", [0, 2], ids=["the dense layer", "last"])
def test_a_layer_left_out_fails_the_tolerance(skip):
    spec, params, tokens = toy("int8")
    served, _ = served_logits(spec, params, tokens)
    layer = ref.layer_of(spec)
    at = [FIRST - 1, FIRST + CHUNK - 1, *range(FIRST + CHUNK, SEQ)]
    with jax.default_matmul_precision("highest"):
        wrong = np.asarray(jnp.stack([ref.logits_at(
            params, spec, ref.hidden_states(params, spec, row, layer,
                                            skip_layer=skip)[jnp.asarray(at)])
            for row in tokens]), np.float32)
    assert distance(served, wrong) > TOLERANCE


@pytest.mark.parametrize("switches, passes", [
    ({"precision": "bfloat16"}, True), ({"precision": "float8_e4m3fn"}, False)],
    ids=["computed in bfloat16", "computed in float8"])
def test_the_reference_in_another_precision(switches, passes):
    spec, params, tokens = toy("int8")
    served, _ = served_logits(spec, params, tokens)
    other = reference_logits(spec, params, tokens, **switches)
    assert (distance(served, other) < TOLERANCE) == passes


@pytest.mark.parametrize("given", ["its own sets", "every key"])
def test_the_reference_takes_its_sets_and_tells_its_scores(given):
    """What benchmark/selection_check.py reads the reference by
    (``selection_logprobs``): ``tell`` hands each layer's float32 index
    scores out, ``keeps`` hands the sets in. Given the sets its own scores choose it returns what it returned;
    given every key, what ``select=false`` returns."""
    spec, params, tokens = toy("int8")
    row, n = tokens[0], SEQ - 4
    told = {}
    own = ref.selection_logprobs(
        params, spec, list(row[:n]), list(row[n:]),
        tell=lambda layer, scores: told.update({layer: np.asarray(scores)}))
    assert sorted(told) == list(range(spec.num_layers))
    assert own == ref.reference_logprobs(params, spec, list(row[:n]),
                                         list(row[n:]))
    size = SEQ - 1
    causal = np.tril(np.ones((size, size), bool))
    if given == "its own sets":
        keeps = [causal.copy() for _ in told]
        for layer, scores in told.items():
            assert scores.shape == (size, size)
            assert np.isneginf(scores[~causal]).all()
            kth = np.sort(scores, axis=1)[:, -spec.index_topk, None]
            keeps[layer] &= scores >= kth
            assert (keeps[layer].sum(1)
                    == np.minimum(np.arange(size) + 1, spec.index_topk)).all()
        want = own
    else:
        keeps = [causal] * spec.num_layers
        want = ref.control_logprobs(params, spec, list(row[:n]),
                                    list(row[n:]), select=False)
    got = ref.selection_logprobs(params, spec, list(row[:n]), list(row[n:]),
                                 keeps=keeps)
    np.testing.assert_allclose(got, want, atol=1e-5)
    if given == "every key":        # and the sets matter at this size
        assert max(abs(a - b) for a, b in zip(got, own)) > 1e-3


def _one_layer(spec, params, x, keys: int):
    """(LatentQuery, entries, index keys) of the first expert layer for
    states x [1, S, H] at positions 0..S-1."""
    lp = jax.tree.map(lambda a: a[0], {
        k: v for k, v in params["layers"].items()
        if not k.startswith(model.DENSE_PREFIX)})
    pos = jnp.arange(keys)[None]
    cos, sin = model.spec_rope_tables(spec, pos)
    h = model.norm(x, lp["input_norm"], spec)
    cq, nope, rope, entry = model.latent_qkv(h, lp, spec, cos, sin)
    iq, iw, ik = model.index_qk(h, cq, lp, spec, cos, sin)
    return (model.LatentQuery(nope, rope, iq, iw, lp["wk_b"], lp["wv_b"]),
            entry, ik)


@pytest.mark.parametrize("quant", [None, "int8"])
def test_the_absorbed_form_equals_the_expanded(quant):
    """Decode folds Wk_b into the query and Wv_b into the output; prefill
    multiplies the latent out. The last position's attention, both ways,
    with the same keys chosen: equal within bfloat16's rounding of the two
    different intermediates (0.04 of the values' own spread; an int8 Wk_b's
    scale multiplies the query in one form and the key in the other)."""
    spec, params, _ = toy(quant)
    keys = 57
    x = jax.random.normal(jax.random.key(5), (1, keys, spec.hidden_size),
                          jnp.bfloat16)
    q, entry, ik = _one_layer(spec, params, x, keys)
    pos = jnp.arange(keys)[None]
    expanded = model.latent_prefill_attention(
        q, entry, ik, pos, jnp.ones((1, keys), bool), spec)[0, -1]
    # The same through a paged pool: 56 keys in pages, the last as self.
    pages = -(-keys // PAGE)
    pad = pages * PAGE - keys
    paged = lambda a: jnp.pad(a[0, :, 0], ((0, pad), (0, 0))).reshape(  # noqa: E731
        1, 1, pages, PAGE, -1)
    last = jax.tree.map(lambda a: a[:, -1] if hasattr(a, "ndim")
                        and a.ndim > 2 and a.shape[1] == keys else a, q)
    absorbed, counts = model.latent_window_attention(
        last, paged(entry), paged(ik), jnp.int32(0),
        jnp.arange(pages, dtype=jnp.int32)[None],
        jnp.asarray([keys - 1], jnp.int32), entry[:, :0].transpose(2, 0, 1, 3),
        ik[:, :0].transpose(2, 0, 1, 3), jnp.int32(0), entry[:, -1],
        ik[:, -1], spec)
    assert counts.tolist() == [40.0, float(keys)]
    a, e = np.asarray(absorbed[0], np.float32), np.asarray(expanded,
                                                           np.float32)
    assert np.abs(a - e).max() < 0.04 * e.std(), np.abs(a - e).max()


def test_the_served_set_is_top_k_s_wherever_the_margin_exceeds_the_gap():
    """bfloat16 states, index keys and queries against float32 ones move a
    score by up to e (here under 0.06 of the scores' spread): wherever the
    float32 scores at ranks index_topk and index_topk + 1 lie further apart
    than the GAP of 2e, e the row's largest move, the served set IS
    ``lax.top_k``'s of the float32 scores."""
    spec, params, _ = toy(None)
    keys = 72
    x = jax.random.normal(jax.random.key(6), (1, keys, spec.hidden_size),
                          jnp.bfloat16)
    q, _, ik = _one_layer(spec, params, x, keys)
    served = np.asarray(model.index_scores(q.iq, q.iw, ik[:, :, 0])[0])
    stack, at = ref.layers_of(params, spec)[spec.first_k_dense]
    layer = jax.tree.map(lambda a: a[at], stack)
    with jax.default_matmul_precision("highest"):
        # The reference's own indexer (step 4), float32 throughout.
        h = ref.rms_norm(x[0].astype(jnp.float32), layer["input_norm"],
                         spec.rms_norm_eps)
        cq = ref.rms_norm(h @ ref.plain(layer["wq_a"]), layer["q_a_norm"],
                          spec.rms_norm_eps)
        freqs = ref.frequencies(8, spec.rope_theta, spec.rope_yarn)
        qi = (cq @ ref.plain(layer["index_wq_b"])).reshape(keys, 16, 16)
        ki = ref.layer_norm_bias(h @ ref.plain(layer["index_wk"]),
                                 layer["index_k_norm"],
                                 layer["index_k_bias"][:, 0])[:, None]
        turn = lambda a: jnp.concatenate(  # noqa: E731
            [ref.rope(a[..., :8], freqs, False), a[..., 8:]], -1)
        qi, ki = turn(qi), turn(ki)[:, 0]
        wi = (h @ layer["index_w"].astype(jnp.float32)) * 256 ** -0.5
        exact = np.asarray(jnp.einsum(
            "tjs,tj->ts", jnp.maximum(jnp.einsum("tjd,sd->tjs", qi, ki), 0),
            wi))
    causal = np.tril(np.ones((keys, keys), bool))
    got = np.asarray(model.select_topk(jnp.asarray(served),
                                       jnp.asarray(causal), 40))
    checked = 0
    for t in range(40, keys):
        row = np.sort(exact[t, :t + 1])[::-1]
        gap = 2 * np.abs(served - exact)[t, :t + 1].max()
        if row[39] - row[40] > gap:
            want = np.zeros(keys, bool)
            want[np.argsort(-exact[t, :t + 1], kind="stable")[:40]] = True
            assert (got[t] == want).all(), t
            checked += 1
    assert checked >= 5
    assert np.abs(served - exact)[causal].max() < 0.06 * exact[causal].std()


# -- the share ------------------------------------------------------------------------

def uncut(seed=5):
    """A toy model that holds all 16 experts, and its four shares of 4."""
    whole = read_spec({**TOY, "n_routed_experts": 16,
                       "expert_parallel": None})
    params = model.init_params(whole, jax.random.key(seed))
    params["layers"]["moe_gate"] = params["layers"]["moe_gate"] * 8.0
    shares = []
    for first in (0, 4, 8, 12):
        spec = dataclasses.replace(whole, num_experts=4, first_expert=first)
        layers = dict(params["layers"])
        for key in ("moe_w_gate", "moe_w_up", "moe_w_down"):
            layers[key] = layers[key][:, first:first + 4]
        shares.append((spec, {**params, "layers": layers}))
    return whole, params, shares


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts of all four shares, with attention and the shared
    expert counted once, are what the uncut reference gives for the whole
    layer: in the reference's own parts, and in the program's block."""
    whole, params, shares = uncut()
    assert whole.router_width == whole.num_experts == 16
    n = 24
    x = jax.random.normal(jax.random.key(9), (n, whole.hidden_size))
    x = x.astype(jnp.bfloat16).astype(jnp.float32)
    index = whole.first_k_dense         # the first expert layer
    with jax.default_matmul_precision("highest"):
        lp = ref.layers_of(params, whole)[index]
        total = ref.layer_of(whole)(x, *lp)
        parts = ref.layer_of(whole, parts=True)(x, *lp)
        routed = sum(ref.layer_of(spec, parts=True)(
            x, *ref.layers_of(p, spec)[index])["routed"]
            for spec, p in shares)
    np.testing.assert_allclose(routed, parts["routed"], atol=1e-5)
    np.testing.assert_allclose(
        x + parts["attention"] + routed + parts["shared"], total, atol=1e-5)
    assert float(jnp.abs(parts["routed"]).mean()) > 0.05

    pos = jnp.arange(n)[None]
    cos, sin = model.spec_rope_tables(whole, pos)

    def block(spec, p):
        lp = jax.tree.map(lambda a: a[0], {
            k: v for k, v in p["layers"].items()
            if not k.startswith(model.DENSE_PREFIX)})

        def attend(q, k, v, kind):
            return model.latent_prefill_attention(
                q, k, v, pos, jnp.ones((1, n), bool), spec)

        y, *_ = model.transformer_block(
            x[None].astype(jnp.bfloat16), lp, spec, cos, sin, attend)
        return np.asarray(y[0], np.float32)

    once = np.asarray(x + parts["attention"] + parts["shared"])
    summed = sum(block(spec, p) for spec, p in shares) - 3 * once
    # bfloat16 against float32: a row whose grouped choice falls the other
    # way moves whole; the others agree.
    off = (np.abs(summed - np.asarray(total)) > 0.15).any(axis=-1)
    assert off.sum() <= 2, off
    assert np.abs(block(*shares[0]) - np.asarray(total)).mean() > 0.02


# -- the engine: counters, prefix reuse, preemption ----------------------------------

def engine_config(spec, **kw) -> EngineConfig:
    return EngineConfig(model=spec, page_size=PAGE, num_pages=96,
                        max_num_seqs=4, max_pages_per_seq=32,
                        decode_window=4, prefill_buckets=(16, 32),
                        max_prefill_tokens=32, prefill_chunk_tokens=16,
                        **kw)


async def _generate(engine, prompt, n, logprobs=True):
    from dynamo_tpu.llm.protocols import (PreprocessedRequest,
                                          SamplingOptions, StopConditions)
    from dynamo_tpu.runtime.context import Context
    req = PreprocessedRequest(
        token_ids=list(prompt), model="toy",
        sampling_options=SamplingOptions(temperature=0.0,
                                         logprobs=0 if logprobs else None),
        stop_conditions=StopConditions(max_tokens=n, ignore_eos=True))
    tokens, lps = [], []
    async for out in engine.generate(req, Context()):
        tokens += out.get("token_ids", [])
        lps += out.get("log_probs") or []
    return tokens, lps


@async_test
async def test_the_engine_serves_it_counts_its_keys_and_reuses_a_prefix():
    """Through TPUEngine: a 72-token prompt (a whole chunk and chunks over
    history) and 12 decoded tokens agree with the reference's logprobs; a
    second request over the same prompt reads cached pages (both arrays
    live under one page id) and the same logprobs; the window's counters
    reach the engine's status."""
    from dynamo_tpu.engine.engine import TPUEngine
    spec, params, tokens = toy(None)
    engine = TPUEngine(engine_config(spec), params=params)
    try:
        prompt = [int(t) for t in tokens[0]]
        got, lps = await _generate(engine, prompt, 12)
        with jax.default_matmul_precision("highest"):
            want = ref.reference_logprobs(params, spec, prompt, got)
            every = ref.control_logprobs(params, spec, prompt, got,
                                         select=False)
        assert len(got) == 12
        # 40 keys of up to 84: a key on the other side of rank 40 in
        # bfloat16 carries a fortieth of a query (TOLERANCE says the same).
        near = np.median(np.abs(np.asarray(lps) - np.asarray(want)))
        far = np.median(np.abs(np.asarray(lps) - np.asarray(every)))
        assert near < 0.2 < far, (near, far)
        hits0 = engine.allocator.stats()["reuse_hit_blocks"]
        again, lps2 = await _generate(engine, prompt, 12)
        assert again == got
        np.testing.assert_allclose(lps2, lps, atol=2e-2)
        # The indexer's instructions carry their scope, fused or not.
        from dynamo_tpu.engine import perf
        scopes = {part for v in perf.get_registry().ops_by_scope(
            "decode_window").values() if v for part in v.split("+")}
        assert "attn.index" in scopes and "moe.router" in scopes
        status = engine.perf_status()
        # Who runs the indexer (the CPU under "auto": XLA's), on the pane,
        # among the window programs' labels and as an info series.
        assert status["index_backend"] == engine.runner.backends.index \
            == status["attention_backend"] == "xla"
        assert "xla" in perf.get_registry().snapshot()["programs"][
            "decode_window"]["labels"]["index_backend"]
        assert {fn._labels["index_backend"] for fn in
                engine.runner._window_cache.values()} == {"xla"}
        attn = status["attn"]
        assert attn["index_topk"] == 40
        assert attn["kv_entry_bytes"] == 3 * (128 + 16) * 2
        assert 0 < attn["selected_pct"] < 100
        # The load counts the two expert layers, never the dense one.
        assert status["moe"]["layer_steps"] % 2 == 0
        assert engine.allocator.stats()["reuse_hit_blocks"] > hits0
    finally:
        engine.stop()


@async_test
async def test_preemption_recomputes_the_latent_pool():
    """Three requests against a pool that cannot hold them: the youngest is
    preempted, requeued and prefilled again from its tokens (entries and
    index keys alike); every stream gets exactly what it asked for, and the
    oldest, never preempted, the tokens it gets alone."""
    from dynamo_tpu.engine.engine import TPUEngine
    spec, params, tokens = toy(None)
    prompts = [[int(t) for t in tokens[i % 2, i:i + 24]] for i in range(3)]
    alone = TPUEngine(engine_config(spec), params=params)
    try:
        want, _ = await _generate(alone, prompts[0], 40, logprobs=False)
    finally:
        alone.stop()
    engine = TPUEngine(dataclasses.replace(engine_config(spec),
                                           num_pages=40), params=params)
    try:
        tasks = []
        for prompt in prompts:
            tasks.append(asyncio.ensure_future(
                _generate(engine, prompt, 40, logprobs=False)))
            await asyncio.sleep(0.05)
        results = await asyncio.gather(*tasks)
        assert engine.preempt_count > 0
        assert [len(toks) for toks, _ in results] == [40, 40, 40]
        assert results[0][0] == want
    finally:
        engine.stop()
