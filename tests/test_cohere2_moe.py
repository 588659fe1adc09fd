"""The Cohere2-MoE block (Command A+: a parallel block over one LayerNorm, a
sigmoid router as wide as the deployment's experts of which this device
holds a share, shared experts averaged, interleaved RoPE on window layers
and none on full ones, a tied head) on the normal path, at toy size on the
CPU: the program's reader on the catalog row's keys and its refusals;
prefill, chunk prefill over history, the decode window and the single decode
step through the paged cache against the plain reference's full forward
(benchmark/references/cohere2_moe.py), on logits, with sequences of 40 at
window 8 and page 4, 128 query heads over 8 KV heads (16 query rows a KV
head); bf16 and int8 weights; the XLA backend and the Pallas kernel in
interpret mode. Each named control must fail the tolerance the program
passes; the shares add up to the uncut layer. Nothing here is a device number.
"""
import asyncio
import dataclasses
import functools
import json
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import async_test
from test_smallthinker import distance

from benchmark.references import cohere2_moe as ref
from dynamo_tpu.engine import model, runner as runner_mod
from dynamo_tpu.engine.backends import XLA, Backends
from dynamo_tpu.engine.config import (PRESETS, Cohere2MoeSpec, EngineConfig,
                                      ModelSpec, UnsupportedBlockError,
                                      block_refusals)
from dynamo_tpu.engine.kv_quant import scatter_tokens
from dynamo_tpu.engine.quant import QUANT_LAYER_KEYS, quantize_params
from dynamo_tpu.engine.runner import ModelRunner, _prefill_with_history

S, F = "sliding_attention", "full_attention"
# The catalog row's ``config`` (model-configs guide, architectures.jsonl:
# command-a-plus-05-2026), verbatim.
CATALOG = {
    "attention_bias": False, "expert_selection_fn": "sigmoid",
    "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 4096, "layer_norm_eps": 1e-05,
    "layer_switch": 4, "layer_types": [S, S, S, F] * 8, "logit_scale": 1,
    "max_position_embeddings": 200000, "model_type": "cohere2_moe",
    "norm_topk_prob": True, "num_attention_heads": 128, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 8, "num_shared_experts": 4,
    "order_of_interleaved_layers": "local_attn_first",
    "position_embedding_type": "rope_gptj",
    "prefix_dense_intermediate_size": 16384,
    "prefix_dense_sliding_window_pattern": 1, "rms_norm_eps": None,
    "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
    "rope_theta": 50000, "rotary_pct": 1,
    "shared_expert_combination_strategy": "average", "sliding_window": 4096,
    "tf_legacy_loss": False, "tie_word_embeddings": True,
    "use_embedding_sharing": True, "use_gated_activation": True,
    "use_parallel_block": True, "use_parallel_embedding": False,
    "use_qk_norm": False, "vocab_size": 262144}
#: The cell's cut: 8 chips share each layer, this one holds share 0.
CUT = {**CATALOG, "num_hidden_layers": 8, "layer_types": [S, S, S, F] * 2,
       "num_experts": 16, "vocab_size": 32768,
       "expert_parallel": {"routed_experts": 128, "first_expert": 0,
                           "chips_per_layer": 8}}
#: One period, 16 query rows a KV head over 8 KV heads, 8 experts routed of
#: which the SECOND half is held (a share that does not start at 0).
TOY = {**CATALOG, "head_dim": 32, "hidden_size": 64, "intermediate_size": 48,
       "num_hidden_layers": 4, "layer_types": [S, S, S, F],
       "num_experts": 4, "num_experts_per_tok": 3, "num_shared_experts": 2,
       "expert_parallel": {"routed_experts": 8, "first_expert": 4},
       "sliding_window": 8, "max_position_embeddings": 2048,
       "vocab_size": 512}
PAGE, SEQ, FIRST, CHUNK, WINDOW = 4, 40, 16, 16, 4


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
    spec = read_spec(CATALOG)
    assert isinstance(spec, Cohere2MoeSpec)
    assert (spec.hidden_size, spec.num_layers, spec.num_heads,
            spec.num_kv_heads, spec.head_dim) == (4096, 32, 128, 8, 128)
    assert (spec.num_experts, spec.router_width, spec.first_expert,
            spec.num_experts_per_tok, spec.num_shared_experts,
            spec.expert_size) == (128, 128, 0, 8, 4, 4096)
    assert spec.moe_router == "sigmoid_topk" and spec.norm_topk_prob
    assert spec.moe_router_input == "post_attn_norm"
    assert spec.ffn_act == "silu" and spec.norm_kind == "layer"
    assert spec.parallel_block and spec.rope_interleaved
    assert spec.rms_norm_eps == 1e-5 and spec.rope_theta == 50000.0
    assert spec.sliding_window == 4096 and spec.tie_word_embeddings
    # Window layers rotate, full layers carry no position.
    assert spec.sliding_window_layout == spec.rope_layout == (1, 1, 1, 0) * 8
    assert spec.has_layer_pattern and spec.vocab_size == 262144
    assert 218.0e9 < spec.num_params() < 218.5e9    # "218B-A25B"


def test_the_cut_holds_16_of_128_experts_and_9_33_gb():
    cut = dataclasses.replace(read_spec(CUT), quant="int8")
    assert (cut.num_experts, cut.router_width, cut.first_expert) == (16, 128,
                                                                     0)
    shapes = model.param_shapes(cut)["layers"]
    assert "post_attn_norm" not in shapes and "lm_head" not in shapes
    assert shapes["moe_gate"] == (8, 4096, 128)
    assert shapes["moe_w_down"] == (8, 16, 4096, 4096)
    assert shapes["shared_w_gate"] == (8, 4, 4096, 4096)
    # A layer: attention 142.6 M, shared 201.3 M, router 0.5 M, 16 experts
    # of 50.33 M; the vocabulary's eighth once (tied).
    layer = (2 * 4096 * 16384 + 2 * 4096 * 1024 + 4 * 3 * 4096 * 4096
             + 4096 * 128 + 16 * 3 * 4096 * 4096 + 4096)
    assert cut.num_params() == 8 * layer + 32768 * 4096 + 4096
    assert 9.32e9 < cut.num_params() < 9.34e9
    assert 11.3 < cut.weight_read_step_ms(819.0) < 11.5
    assert set(QUANT_LAYER_KEYS) >= {"shared_w_gate", "shared_w_up",
                                     "shared_w_down"}
    # The page "auto" derives from 8 KV heads of 128.
    config = EngineConfig(model=cut)
    assert config.resolve_page_size("tpu") == 32
    assert config.resolve_page_size("cpu") == 16


@pytest.mark.parametrize("spec", [
    read_spec(TOY), read_spec(CUT), read_spec(CATALOG),
    PRESETS["tiny-test"], PRESETS["qwen2.5-0.5b"],
    ModelSpec(num_experts=8, hidden_size=64, intermediate_size=32,
              num_layers=2, num_heads=4, num_kv_heads=2, vocab_size=128)],
    ids=["toy", "cut", "published", "tiny", "qwen bias tied", "mixtral"])
def test_num_params_is_the_sum_of_param_shapes(spec):
    assert spec.num_params() == count(model.param_shapes(spec))


@pytest.mark.parametrize("key, value, says", [
    ("use_qk_norm", True, "use_qk_norm"),
    ("first_k_dense_replace", 2, "first_k_dense_replace"),
    ("shared_expert_combination_strategy", "sum", "averaged"),
    ("expert_selection_fn", "softmax", "router kinds"),
    ("use_parallel_block", False, "sequential"),
    ("position_embedding_type", "rope_neox", "interleaved"),
    ("layer_types", ["chunked_attention"] * 32, "layer kinds"),
    ("rope_parameters", {"rope_type": "yarn"}, "scaled RoPE")])
def test_the_reader_refuses_what_it_cannot_express(key, value, says):
    with pytest.raises(UnsupportedBlockError, match=says):
        read_spec({**CATALOG, key: value})


def test_a_share_has_to_lie_inside_the_routers_width():
    with pytest.raises(ValueError, match="not among the router's 8"):
        read_spec({**TOY, "expert_parallel": {"routed_experts": 8,
                                              "first_expert": 6}})
    with pytest.raises(ValueError, match="layout has 4 entries"):
        read_spec({**TOY, "num_hidden_layers": 8})


# -- the program against the reference, on logits ---------------------------------

@functools.cache
def toy(quant: str | None, seed: int = 3):
    spec = dataclasses.replace(read_spec(TOY), quant=quant)
    params = model.init_params(spec, jax.random.key(seed))
    # A router whose choices are decided, so that bfloat16 against float32
    # flips few experts at this toy width.
    params["layers"]["moe_gate"] = params["layers"]["moe_gate"] * 8.0
    if quant:
        params = jax.tree.map(jnp.asarray, quantize_params(
            jax.tree.map(np.asarray, params)))
    tokens = np.asarray(jax.random.randint(
        jax.random.key(seed + 1), (2, SEQ), 1, spec.vocab_size), np.int32)
    return spec, params, tokens


def served_logits(spec, params, tokens, backend: str) -> np.ndarray:
    """Logits the program gives after positions FIRST-1 (whole-prompt
    prefill), FIRST+CHUNK-1 (chunk prefill over cached history), then one
    row a decoded position: WINDOW steps of the window program with its
    commit, the rest by the single decode step; [B, rows, V]."""
    b = tokens.shape[0]
    pages = SEQ // PAGE
    kv = jnp.zeros((spec.num_layers, spec.num_kv_heads, b * pages + 1, PAGE,
                    spec.head_dim), jnp.bfloat16)
    table = (1 + np.arange(b * pages, dtype=np.int32)).reshape(b, pages)
    # The record a runner would hand the programs: XLA's, or the kernels
    # interpreted.
    record = XLA if backend == "xla" else Backends(attention="pallas",
                                                   interpret=True)
    rows = []
    pos = np.broadcast_to(np.arange(FIRST, dtype=np.int32), (b, FIRST))
    lens = np.full((b,), FIRST, np.int32)
    logits, k, v = jax.jit(lambda p, k, v: model.prefill_forward(
        p, spec, k, v, tokens[:, :FIRST], pos, table[:, :FIRST // PAGE],
        lens))(params, kv, kv + 0)
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
        L, nkv, d = spec.num_layers, spec.num_kv_heads, spec.head_dim
        kbuf = jnp.zeros((L, nkv, b, WINDOW, d), k.dtype)
        vbuf = jnp.zeros_like(kbuf)
        hist = jnp.full((b,), done, jnp.int32)
        out = []
        for m in range(WINDOW):
            logits, k_new, v_new, stats = model.decode_window_step(
                p, spec, k, v, kbuf, vbuf, jnp.int32(m),
                tokens[:, done + m], hist + m, table, hist,
                backends=record, live=jnp.ones((b,), bool))
            kbuf = kbuf.at[:, :, :, m].set(k_new.transpose(0, 2, 1, 3))
            vbuf = vbuf.at[:, :, :, m].set(v_new.transpose(0, 2, 1, 3))
            out.append(logits)
        at = done + np.arange(WINDOW)
        dest = jnp.asarray(table[:, at // PAGE].T)          # [M, B]
        off = jnp.broadcast_to(jnp.asarray(at % PAGE)[:, None], dest.shape)
        k = scatter_tokens(k, kbuf.transpose(0, 1, 3, 2, 4), dest, off)
        v = scatter_tokens(v, vbuf.transpose(0, 1, 3, 2, 4), dest, off)
        return jnp.stack(out), k, v, stats

    logits, k, v, stats = jax.jit(window)(params, k, v)
    rows += list(logits)
    done += WINDOW
    stats = np.asarray(stats["moe"])                        # [L, 5]
    k_tok = spec.num_experts_per_tok
    assert stats.shape == (spec.num_layers, 5) and (stats[:, 2] == 1).all()
    # Touched and the picks are counted over the experts HELD.
    assert (stats[:, 0] <= spec.num_experts).all()
    assert (stats[:, 4] == b * k_tok).all()
    assert (stats[:, 3] <= stats[:, 4]).all() and stats[:, 3].sum() > 0
    assert (stats[:, 0] <= stats[:, 3]).all()
    decode = jax.jit(lambda p, k, v, t, at: model.decode_forward(
        p, spec, k, v, t, at, table, at + 1, backends=record))
    while done < SEQ:
        logits, k, v = decode(params, k, v, tokens[:, done],
                              np.full((b,), done, np.int32))
        rows.append(logits)
        done += 1
    return np.asarray(jnp.stack(rows, axis=1), np.float32)


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


CONTROLS = {"rotate-half RoPE": {"interleaved": False},
            "softmax for sigmoid": {"sigmoid": False},
            "gates normalised over the held experts": {"norm_over": "held"},
            "shared experts left out": {"shared": "none"},
            "(routed + shared) / 2": {"shared": "halved"},
            "sequential residual": {"parallel": False},
            "no window": {"use_window": False},
            "RoPE on full layers too": {"use_nope": False}}
#: Between what the program reads and what the controls read (the test
#: below prints both): nats of a toy on the CPU, no device number.
TOLERANCE = 0.03


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("quant", [None, "int8"])
def test_prefill_then_decode_agrees_with_the_reference_across_the_window(
        quant, backend):
    spec, params, tokens = toy(quant)
    assert spec.q_per_kv == 16 and spec.num_kv_heads == 8
    served = served_logits(spec, params, tokens, backend)
    full = reference_logits(spec, params, tokens)
    assert served.shape == full.shape == (2, 2 + SEQ - FIRST - CHUNK,
                                          spec.vocab_size)
    assert distance(served, full) < TOLERANCE


@pytest.mark.parametrize("control", sorted(CONTROLS))
@pytest.mark.parametrize("quant", [None, "int8"])
def test_each_control_fails_the_same_tolerance(quant, control):
    spec, params, tokens = toy(quant)
    served = served_logits(spec, params, tokens, "xla")
    wrong = reference_logits(spec, params, tokens, **CONTROLS[control])
    assert distance(served, wrong) > TOLERANCE, control


@pytest.mark.parametrize("skip", [0, 3], ids=["first", "last"])
def test_a_layer_left_out_fails_the_tolerance(skip):
    spec, params, tokens = toy("int8")
    served = served_logits(spec, params, tokens, "xla")
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
    """The nearest precision below the configuration's, float8, falls
    outside the tolerance: the control the chip's ALLOWED_NATS is set
    against. The whole forward in bfloat16 stays inside it."""
    spec, params, tokens = toy("int8")
    served = served_logits(spec, params, tokens, "xla")
    other = reference_logits(spec, params, tokens, **switches)
    assert (distance(served, other) < TOLERANCE) == passes
    # Tighter than the dense block's limits, under the smallest float8 read.
    from benchmark.lib import reference as plainref
    assert ref.ALLOWED_NATS["median"] < plainref.ALLOWED_NATS["median"]
    assert ref.ALLOWED_NATS["median"] < 0.0252 and \
        ref.ALLOWED_NATS["rms"] < 0.0406


# -- the share ------------------------------------------------------------------------

def uncut(quant=None, seed=5):
    """A toy layer that holds all 8 experts, and its two shares of 4."""
    whole = dataclasses.replace(
        read_spec({**TOY, "num_experts": 8, "expert_parallel": None}),
        quant=quant)
    params = model.init_params(whole, jax.random.key(seed))
    params["layers"]["moe_gate"] = params["layers"]["moe_gate"] * 8.0
    shares = []
    for first in (0, 4):
        spec = dataclasses.replace(whole, num_experts=4, first_expert=first)
        layers = dict(params["layers"])
        for key in ("moe_w_gate", "moe_w_up", "moe_w_down"):
            layers[key] = layers[key][:, first:first + 4]
        shares.append((spec, {**params, "layers": layers}))
    return whole, params, shares


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts of all the shares, with attention and the shared
    experts counted once, are what the uncut reference gives for the whole
    layer: in the reference's own parts, and in the program's block."""
    whole, params, shares = uncut()
    assert whole.router_width == whole.num_experts == 8
    x = jax.random.normal(jax.random.key(9), (24, whole.hidden_size))
    x = x.astype(jnp.bfloat16).astype(jnp.float32)
    index = jnp.int32(1)
    with jax.default_matmul_precision("highest"):
        total = ref.layer_of(whole)(x, params["layers"], index)
        parts = ref.layer_of(whole, parts=True)(x, params["layers"], index)
        routed = sum(ref.layer_of(spec, parts=True)(
            x, p["layers"], index)["routed"] for spec, p in shares)
    np.testing.assert_allclose(routed, parts["routed"], atol=1e-5)
    np.testing.assert_allclose(
        x + parts["attention"] + routed + parts["shared"], total, atol=1e-5)
    assert float(jnp.abs(parts["routed"]).mean()) > 0.05

    # The program's block on each share: y_s = x + a + routed_s + shared.
    pos = jnp.arange(24)[None]
    cos, sin = model.rope_tables(pos, whole.head_dim, whole.rope_theta)

    def block(spec, p):
        lp = jax.tree.map(lambda a: a[1], p["layers"])

        def attend(q, k, v, kind):
            return model.dense_causal_attention(
                q, k, v, pos, jnp.ones((1, 24), bool), spec.q_per_kv,
                reach=model.window_reach(spec, kind)).reshape(1, 24, -1)

        y, *_ = model.transformer_block(
            x[None].astype(jnp.bfloat16), lp, spec, cos, sin, attend,
            model.layer_kind(spec, index))
        return np.asarray(y[0], np.float32)

    once = np.asarray(x + parts["attention"] + parts["shared"])
    summed = sum(block(spec, p) for spec, p in shares) - once
    np.testing.assert_allclose(summed, np.asarray(total), atol=0.12)
    assert np.abs(block(*shares[0]) - np.asarray(total)).mean() > 0.02


def test_gates_are_normalised_over_all_the_chosen_wherever_they_are_held():
    """A row's k gates sum to one over ALL its chosen experts, so the ones
    held here sum to less wherever a choice fell elsewhere; normalised over
    the held ones alone they would sum to one on every row."""
    whole, params, shares = uncut()
    spec, p = shares[1]
    lp = jax.tree.map(lambda a: a[0], p["layers"])
    h = jax.random.normal(jax.random.key(2), (32, 64), jnp.bfloat16)
    router = jnp.einsum("th,he->te", h, lp["moe_gate"],
                        preferred_element_type=jnp.float32)
    gates, top_i = model.moe_route(router, spec)
    assert top_i.shape == (32, 3) and int(top_i.max()) > 3   # of all 8
    np.testing.assert_allclose(gates.sum(-1), 1.0, atol=1e-6)
    held = (top_i >= 4) & (top_i < 8)
    mass = np.asarray((gates * held).sum(-1))
    assert (mass < 0.999).any() and (mass <= 1.0 + 1e-6).all()
    # The layer's output IS that partial sum: a row none of whose choices is
    # held here gets the shared experts' mean alone.
    none = np.asarray(~held.any(-1))
    out = np.asarray(model.ffn_block(h, lp, spec), np.float32)
    with jax.default_matmul_precision("highest"):
        shared = jnp.mean(jnp.stack([
            (jax.nn.silu(h.astype(jnp.float32) @ lp["shared_w_gate"][j]
                         .astype(jnp.float32))
             * (h.astype(jnp.float32) @ lp["shared_w_up"][j]
                .astype(jnp.float32)))
            @ lp["shared_w_down"][j].astype(jnp.float32)
            for j in range(2)]), axis=0)
    if none.any():
        np.testing.assert_allclose(out[none], np.asarray(shared)[none],
                                   atol=0.03)
    assert np.abs(out[~none] - np.asarray(shared)[~none]).mean() > 0.02


def test_load_stats_count_the_held_experts():
    spec = read_spec(TOY)                       # holds experts 4 to 7 of 8
    top_i = jnp.asarray([[4, 5, 0], [4, 1, 2], [7, 4, 3], [0, 1, 2]])
    one_hot = jax.nn.one_hot(top_i - spec.first_expert, spec.num_experts)
    live = jnp.asarray([True, True, True, False])
    touched, load, some, local, picks = np.asarray(
        model.moe_load_stats(*model.held_load(one_hot, live)[::2], live,
                             spec))
    assert (touched, some, local, picks) == (3, 1, 5, 9)
    # Expert 4 holds 3 tokens; an even router gives 3 rows x 3 / 8 each.
    assert load == pytest.approx(3 / (9 / 8))
    # A block that holds every expert keeps its three sums.
    mixtral = ModelSpec(num_experts=8, num_experts_per_tok=3)
    assert model.moe_load_stats(
        *model.held_load(jax.nn.one_hot(top_i, 8), live)[::2], live,
        mixtral).shape == (3,)


def test_a_long_batch_of_a_share_takes_the_kernel_by_its_own_pairs(
        monkeypatch):
    """Above MOE_DENSE_MAX_ROWS a share sorts its pairs by the expert HELD
    and multiplies each group by its own expert; a pair whose expert is held
    elsewhere sorts behind the last group and adds nothing, as under the
    masked product."""
    spec, params, _ = toy("int8")
    lp = jax.tree.map(lambda a: a[2], params["layers"])
    x = jax.random.normal(jax.random.key(1), (100, 64), jnp.bfloat16)
    calls = []
    real = model._grouped_experts
    monkeypatch.setattr(model, "_grouped_experts",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    outs = []
    monkeypatch.setattr(model, "MOE_DENSE_MAX_ROWS", 32)
    for record in (Backends(experts_whole=True, interpret=True), Backends()):
        outs.append(np.asarray(jax.jit(lambda x: model.ffn_block(
            x, lp, spec, backends=record))(x), np.float32))
        assert len(calls) == 1          # where the experts are whole alone
    assert np.abs(outs[1]).mean() > 0.1
    np.testing.assert_allclose(outs[0], outs[1], atol=0.02)


def test_history_attention_a_kv_head_at_a_time_is_the_same(monkeypatch):
    """Above HISTORY_SCORE_BYTES the with-history prefill computes a KV
    head's scores at a time (128 query heads over a long history do not
    fit at once); the logits are those of all heads at once."""
    spec, params, tokens = toy(None)
    b, pages = 2, SEQ // PAGE
    kv = jnp.zeros((spec.num_layers, spec.num_kv_heads, b * pages + 1, PAGE,
                    spec.head_dim), jnp.bfloat16)
    table = (1 + np.arange(b * pages, dtype=np.int32)).reshape(b, pages)
    pos = np.broadcast_to(np.arange(FIRST, dtype=np.int32), (b, FIRST))
    _, k, v = jax.jit(lambda p, k, v: model.prefill_forward(
        p, spec, k, v, tokens[:, :FIRST], pos, table[:, :FIRST // PAGE],
        np.full((b,), FIRST, np.int32)))(params, kv, kv + 0)
    got = []
    for limit in (1 << 30, 0):
        monkeypatch.setattr(runner_mod, "HISTORY_SCORE_BYTES", limit)
        logits, _, _ = jax.jit(lambda p, k, v: _prefill_with_history(
            p, spec, k, v, tokens[:, FIRST:FIRST + CHUNK], pos + FIRST,
            table[:, FIRST // PAGE:(FIRST + CHUNK) // PAGE],
            np.full((b,), CHUNK, np.int32), table[:, :FIRST // PAGE],
            np.full((b,), FIRST, np.int32), XLA))(params, k, v)
        got.append(np.asarray(logits, np.float32))
    np.testing.assert_allclose(got[0], got[1], atol=2e-2)
    assert np.abs(got[0]).mean() > 0.1


# -- refusals ---------------------------------------------------------------------------

BASE = dict(page_size=PAGE, num_pages=32, max_pages_per_seq=16,
            max_num_seqs=2, prefill_buckets=(16, 32),
            attention_backend="xla")


@pytest.mark.parametrize("asked, path, lacks", [
    ({"spec_decode": "ngram"}, "spec_decode", "no window mask"),
    ({"ring_attention": True}, "ring attention", "no window mask"),
    ({"pp_microbatch": True}, "pipelined prefill", "no global layer index"),
    ({"max_adapters": 2}, "LoRA", "never compared with its reference"),
    ({"tp": 2}, "mesh", "no exchange of rows"),
    ("checkpoint", "safetensors loader", "tensor-name map"),
    ("embeddings", "encoder embeddings", "no vision or audio tower")])
def test_paths_that_cannot_run_the_block_refuse_it_by_name(asked, path,
                                                           lacks):
    spec, params, _ = toy(None)
    if asked == "checkpoint":
        found = block_refusals(spec, checkpoint=True)
        from dynamo_tpu.engine.weights import load_hf_weights
        start = lambda: load_hf_weights(spec, "/nonexistent")  # noqa: E731
    elif asked == "embeddings":
        found = block_refusals(spec, embeddings=True)
        start = None
    else:
        config = EngineConfig(model=spec, **BASE, **asked)
        found = block_refusals(spec, config)[:1]
        start = lambda: ModelRunner(config, params=params)  # noqa: E731
    assert len(found) == 1 and path in str(found[0]) \
        and lacks in str(found[0])
    if start is not None:
        with pytest.raises(UnsupportedBlockError, match=lacks) as caught:
            start()
        assert str(caught.value) == str(found[0])
    # The normal path takes the block; no other block is refused embeddings.
    assert block_refusals(spec, EngineConfig(model=spec, **BASE)) == []
    assert block_refusals(PRESETS["tiny-test"], embeddings=True) == []


# -- through the engine: counters, series and scopes of this block alone -----------------

@async_test(timeout=300)
async def test_the_engine_serves_the_share_and_counts_its_picks():
    from dynamo_tpu.engine.engine import TPUEngine
    from dynamo_tpu.engine.perf import PerfMetricsUpdater
    from dynamo_tpu.llm.protocols import PreprocessedRequest
    from dynamo_tpu.runtime import flight
    from dynamo_tpu.runtime.context import Context
    from dynamo_tpu.runtime.metrics import MetricsRegistry
    spec, params, _ = toy(None)
    ring = flight.get_recorder()
    ring.thaw()
    ring.clear()
    engine = TPUEngine(EngineConfig(
        model=spec, page_size=PAGE, num_pages=128, max_pages_per_seq=32,
        max_num_seqs=4, prefill_buckets=(16, 32, 64), max_prefill_tokens=64,
        attention_backend="xla", decode_window=4), params=params)

    async def generate(prompt: int, n: int, **extra) -> list:
        req = PreprocessedRequest(model="m",
                                  token_ids=list(range(1, prompt + 1)))
        req.stop_conditions.max_tokens = n
        req.stop_conditions.ignore_eos = True
        for key, value in extra.items():
            setattr(req, key, value)
        got = []
        async for out in engine.generate(req, Context()):
            got.extend(out.get("token_ids", []))
        return got

    try:
        t_lo = time.monotonic()
        a, b = await asyncio.gather(generate(20, 21), generate(12, 13))
        assert (len(a), len(b)) == (21, 13)
        await asyncio.sleep(0.05)
        touched, load, n, local, picks = (
            engine.counts_total[c] for c, _ in flight.COUNTS["moe"])
        assert n > 0 and n % spec.num_layers == 0
        # 1 or 2 live rows of 3 choices among 8, of which 4 are held.
        assert 3 * n <= picks <= 6 * n and 0 < local < picks
        assert touched <= local and touched <= 4 * n
        moe = engine.perf_status()["moe"]
        assert (moe["experts"], moe["experts_routed"], moe["first_expert"],
                moe["experts_shared"]) == (4, 8, 4, 2)
        assert moe["local_picks_pct"] == pytest.approx(100 * local / picks,
                                                       abs=1e-3)
        cols = ring.between(t_lo, time.monotonic())["columns"]
        assert cols["moe_layer_steps"].sum() == n
        assert cols["moe_local_picks"].sum() == pytest.approx(local)
        assert cols["moe_picks"].sum() == pytest.approx(picks)
        registry = MetricsRegistry()
        PerfMetricsUpdater(registry).update(engine, force=True)
        text = registry.expose().decode()
        for series in ("moe_local_picks_total{", "moe_picks_total{",
                       'moe_experts_info{', 'kind="held"'):
            assert series in text, series
        with pytest.raises(UnsupportedBlockError, match="mm_embeds"):
            await generate(8, 2, mm_embeds=[{"start": 0}])
    finally:
        engine.stop()


def test_no_other_block_carries_the_share_s_counters_or_scope():
    """A SmallThinker program sums three numbers and draws no moe.shared
    scope; this block's sums five and draws it."""
    from test_smallthinker import toy as smallthinker_toy
    for (spec, params, _), width, shared in (
            (smallthinker_toy(None), 3, False), (toy(None), 5, True)):
        b = 2
        kv = jnp.zeros((spec.num_layers, spec.num_kv_heads, 9, PAGE,
                        spec.head_dim), jnp.bfloat16)
        kbuf = jnp.zeros((spec.num_layers, spec.num_kv_heads, b, WINDOW,
                          spec.head_dim), jnp.bfloat16)
        table = np.arange(1, 9, dtype=np.int32).reshape(2, 4)
        at = np.full((b,), 3, np.int32)
        lowered = jax.jit(lambda p, k, v: model.decode_window_step(
            p, spec, k, v, kbuf, kbuf, jnp.int32(0), at, at, table, at,
            live=jnp.ones((b,), bool))).lower(params, kv, kv)
        assert jax.eval_shape(lambda p, k, v: model.decode_window_step(
            p, spec, k, v, kbuf, kbuf, jnp.int32(0), at, at, table, at,
            live=jnp.ones((b,), bool)), params, kv, kv)[3]["moe"].shape == (
            spec.num_layers, width)
        text = lowered.as_text(debug_info=True)
        assert ("moe.shared" in text) == shared
        assert "moe.experts" in text
