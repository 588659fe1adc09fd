"""The SmallThinker block (a router ahead of attention over ReGLU experts,
NoPE global layers among RoPE sliding-window layers) on the normal path, at
toy size on the CPU: the program's reader on the catalog row's keys; prefill,
chunk prefill over history, the decode window and the single decode step
through the paged cache against the plain reference's full forward
(benchmark/references/smallthinker.py), on logits, with sequences of 40 at
window 8 and page 4 so every window layer bites; bf16 and int8 weights; the
XLA backend and the Pallas kernel in interpret mode. Each control (the
reference with ONE equation switched to what a careless port would compute)
must fail the tolerance the program passes. Nothing here is a device number.
"""
import asyncio
import dataclasses
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import async_test

from benchmark.lib import reference as plainref
from benchmark.references import smallthinker as ref
from dynamo_tpu.engine import model
from dynamo_tpu.engine.backends import XLA, Backends
from dynamo_tpu.engine.attention import paged_decode_attention_pallas
from dynamo_tpu.engine.config import (PRESETS, EngineConfig, ModelSpec,
                                      UnsupportedBlockError, block_refusals)
from dynamo_tpu.engine.kv_quant import scatter_tokens
from dynamo_tpu.engine.quant import quantize_params
from dynamo_tpu.engine.runner import ModelRunner, _prefill_with_history

#: A runner's record on one CPU device, as the expert layer reads it.
WHOLE = Backends(experts_whole=True, interpret=True)

# The catalog row's ``config`` (model-configs guide, architectures.jsonl:
# SmallThinker-21BA3B-Instruct), layouts written as their period of four.
CATALOG = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936}

TOY = {**CATALOG, "head_dim": 32, "hidden_size": 64,
       "moe_ffn_hidden_size": 48, "moe_num_active_primary_experts": 3,
       "moe_num_primary_experts": 8, "num_attention_heads": 4,
       "num_hidden_layers": 8, "num_key_value_heads": 2,
       "rope_layout": [0, 1, 1, 1] * 2,
       "sliding_window_layout": [0, 1, 1, 1] * 2, "sliding_window_size": 8,
       "max_position_embeddings": 2048, "vocab_size": 512}
PAGE, SEQ, FIRST, CHUNK, WINDOW = 4, 40, 16, 16, 4


def read_spec(tmp_path, cfg: dict) -> ModelSpec:
    path = tmp_path / "config.json"
    path.write_text(json.dumps(cfg))
    return ModelSpec.from_hf_config(str(path))


def test_from_hf_config_reads_the_catalog_rows_keys(tmp_path):
    spec = read_spec(tmp_path, CATALOG)
    assert (spec.hidden_size, spec.num_layers, spec.num_heads,
            spec.num_kv_heads, spec.head_dim) == (2560, 52, 28, 4, 128)
    assert (spec.num_experts, spec.num_experts_per_tok,
            spec.expert_size) == (64, 6, 768)
    assert spec.moe_router == "softmax_topk" and spec.norm_topk_prob
    assert spec.moe_router_input == "layer_input" and spec.ffn_act == "relu"
    assert spec.sliding_window == 4096 and spec.rope_theta == 1.5e6
    assert spec.rope_layout == spec.sliding_window_layout == (0, 1, 1, 1) * 13
    assert spec.has_layer_pattern and not spec.qkv_bias
    assert not spec.tie_word_embeddings and spec.vocab_size == 151936
    # The expert width is what the sizes count: 64 x 3 x 2560 x 768 a layer.
    per_layer = (2 * 2560 * 28 * 128 + 2 * 2560 * 4 * 128 + 2560 * 64
                 + 64 * 3 * 2560 * 768 + 2 * 2560)
    assert spec.num_params() == 52 * per_layer + 2 * 151936 * 2560 + 2560
    cut = dataclasses.replace(
        read_spec(tmp_path, {**CATALOG, "num_hidden_layers": 24,
                             "rope_layout": [0, 1, 1, 1] * 6,
                             "sliding_window_layout": [0, 1, 1, 1] * 6}),
        quant="int8")
    assert 12.5 < cut.weight_read_step_ms(819.0) < 12.7
    assert model.param_shapes(cut)["layers"]["moe_w_down"] == (
        24, 64, 768, 2560)
    with pytest.raises(ValueError, match="_layout has 52 entries"):
        read_spec(tmp_path, {**CATALOG, "num_hidden_layers": 24})
    with pytest.raises(UnsupportedBlockError, match="softmax"):
        read_spec(tmp_path, {**CATALOG,
                             "moe_primary_router_apply_softmax": False})


def test_the_dense_and_mixtral_readings_do_not_change(tmp_path):
    """Field by field: an unequal spec misses every cached program."""
    qwen = read_spec(tmp_path, {
        "model_type": "qwen2", "hidden_size": 3584,
        "intermediate_size": 18944, "num_hidden_layers": 28,
        "num_attention_heads": 28, "num_key_value_heads": 4,
        "vocab_size": 152064, "rope_theta": 1000000.0,
        "rms_norm_eps": 1e-06, "max_position_embeddings": 32768,
        "tie_word_embeddings": False, "_name_or_path": "q"})
    want = ModelSpec(name="q", vocab_size=152064, hidden_size=3584,
                     intermediate_size=18944, num_layers=28, num_heads=28,
                     num_kv_heads=4, head_dim=128, rope_theta=1000000.0,
                     rms_norm_eps=1e-06, qkv_bias=True,
                     max_position_embeddings=32768)
    assert qwen == want
    mixtral = read_spec(tmp_path, {
        "model_type": "mixtral", "hidden_size": 256,
        "intermediate_size": 512, "num_hidden_layers": 4,
        "num_attention_heads": 8, "num_key_value_heads": 4,
        "num_local_experts": 8, "num_experts_per_tok": 2,
        "vocab_size": 4096, "_name_or_path": "m"})
    assert mixtral == ModelSpec(
        name="m", vocab_size=4096, hidden_size=256, intermediate_size=512,
        num_layers=4, num_heads=8, num_kv_heads=4, num_experts=8,
        num_experts_per_tok=2)
    assert mixtral.expert_size == 512
    for spec in (qwen, mixtral, *PRESETS.values()):
        assert not spec.has_layer_pattern
        # Llama's, Qwen2's and Mixtral's block is refused nowhere.
        every = EngineConfig(model=spec, tp=2, spec_decode="ngram",
                             max_adapters=2, ring_attention=True,
                             pp_microbatch=True)
        assert block_refusals(spec, every, checkpoint=True) == []
        assert model.layer_kind(spec, 0) is None


# -- the program against the reference, on logits ---------------------------------

@functools.cache
def toy(quant: str | None, seed: int = 3):
    import os
    import tempfile
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "config.json")
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(TOY, fh)
        spec = dataclasses.replace(ModelSpec.from_hf_config(path),
                                   quant=quant)
    params = model.init_params(spec, jax.random.key(seed))
    # A router whose choices are decided (logits an order apart), so that
    # bfloat16 against float32 flips few experts at this toy width.
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
    stats = np.asarray(stats["moe"])                        # [L, 3]
    assert stats.shape == (spec.num_layers, 3) and (stats[:, 2] == 1).all()
    assert (1 <= stats[:, 0]).all() and (stats[:, 0] <= min(
        spec.num_experts, b * spec.num_experts_per_tok)).all()
    assert (stats[:, 1] >= 1.0 - 1e-6).all()
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
            x = plainref.plain(params["embed"])[row] if not hasattr(
                params["embed"], "q") else (
                params["embed"].q[row].astype(jnp.float32)
                * params["embed"].s[0])
            for index in range(spec.num_layers):
                x = layer(x, params["layers"], jnp.int32(index))
            h = plainref.rms_norm(x[jnp.asarray(at)], params["final_norm"],
                                  float(spec.rms_norm_eps))
            out.append(h @ plainref.plain(params["lm_head"]))
    return np.asarray(jnp.stack(out), np.float32)


def distance(a: np.ndarray, b: np.ndarray) -> float:
    """Median over the rows of the root mean square difference of the two
    log-softmax rows: one flipped expert moves one row, a wrong equation
    moves most."""
    la = jax.nn.log_softmax(jnp.asarray(a), axis=-1)
    lb = jax.nn.log_softmax(jnp.asarray(b), axis=-1)
    return float(jnp.median(jnp.sqrt(jnp.mean((la - lb) ** 2, axis=-1))))


CONTROLS = {"no window": {"use_window": False},
            "RoPE on every layer": {"use_nope": False},
            "router fed the normalised state": {"router_reads_input": False},
            "SiLU for ReLU": {"relu": False},
            "top-k without renormalising": {"renorm": False}}
#: Between what the program reads (0.029 to 0.037 over bf16 and int8, both
#: backends) and what the controls read (0.755 to 1.14): four times the
#: largest of the one, a fifth of the smallest of the other (builder's CPU
#: runs, PR 28; nats of a toy, no device number).
TOLERANCE = 0.15


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("quant", [None, "int8"])
def test_prefill_then_decode_agrees_with_the_reference_across_the_window(
        quant, backend):
    spec, params, tokens = toy(quant)
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


@pytest.mark.parametrize("switches, passes", [
    ({"router": "float32"}, True), ({"precision": "bfloat16"}, True),
    ({"precision": "float8_e4m3fn"}, False)],
    ids=["float32 router", "computed in bfloat16", "computed in float8"])
def test_the_reference_in_another_precision(switches, passes):
    """The reference reads the router's input as bfloat16 holds it and
    computes the rest in float32. With this toy's decisive router the
    float32 router and the whole forward in bfloat16 (the stream, q, k, v
    and every product's input rounded) stay inside the tolerance; the
    nearest precision below, float8, falls outside it: the control the
    chip's ALLOWED_NATS is set against."""
    spec, params, tokens = toy("int8")
    served = served_logits(spec, params, tokens, "xla")
    other = reference_logits(spec, params, tokens, **switches)
    assert (distance(served, other) < TOLERANCE) == passes
    low, high = ref.ALLOWED_NATS["median"], plainref.ALLOWED_NATS["median"]
    assert high < low < 0.273     # dense limit < this < smallest float8 read
    x = jax.random.normal(jax.random.key(0), (5, spec.hidden_size))
    exact = x.astype(jnp.bfloat16).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        a, b = (ref.layer_of(spec, router=r)(
            exact, params["layers"], jnp.int32(1))
            for r in ("bfloat16", "float32"))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


BASE = dict(page_size=PAGE, num_pages=32, max_pages_per_seq=16,
            max_num_seqs=2, prefill_buckets=(16, 32),
            attention_backend="xla")


@pytest.mark.parametrize("asked, path, lacks", [
    ({"spec_decode": "ngram"}, "spec_decode", "no window mask"),
    ({"ring_attention": True}, "ring attention", "no window mask"),
    ({"pp_microbatch": True}, "pipelined prefill", "no global layer index"),
    ({"max_adapters": 2}, "LoRA", "never compared with its reference"),
    ({"tp": 2}, "mesh", "never compared with its reference"),
    ("checkpoint", "safetensors loader", "tensor-name map")])
def test_paths_that_cannot_run_the_block_refuse_it_by_name(asked, path,
                                                           lacks):
    """One refusal each, from the one function that holds them all; the
    message names the path and the mechanism it lacks, and whoever owns
    the path raises it at start-up."""
    spec, params, _ = toy(None)
    if asked == "checkpoint":
        found = block_refusals(spec, checkpoint=True)
        from dynamo_tpu.engine.weights import load_hf_weights
        start = lambda: load_hf_weights(spec, "/nonexistent")  # noqa: E731
    else:
        config = EngineConfig(model=spec, **BASE, **asked)
        found = block_refusals(spec, config)
        start = lambda: ModelRunner(config, params=params)  # noqa: E731
    assert len(found) == 1 and path in str(found[0]) \
        and lacks in str(found[0])
    with pytest.raises(UnsupportedBlockError, match=lacks) as caught:
        start()
    assert str(caught.value) == str(found[0])


def test_a_refusal_tests_the_field_that_carries_the_mechanism():
    """No model's name decides: a dense block with a window layer is
    refused by the two paths without a window mask and by the stage scan,
    and by nothing that only the routed block was never compared on; the
    normal path takes the SmallThinker block, and LoRA's shapes are asked
    of it without a refusal of their own."""
    spec, params, _ = toy(None)
    windowed = dataclasses.replace(
        spec, num_experts=0, moe_router="topk_softmax",
        moe_router_input="post_attn_norm", ffn_act="silu", rope_layout=None)
    asked = EngineConfig(model=windowed, tp=2, spec_decode="ngram",
                         max_adapters=2, ring_attention=True,
                         pp_microbatch=True)
    said = [str(r) for r in block_refusals(windowed, asked, checkpoint=True)]
    assert sum("no window mask" in m for m in said) == 2
    assert sum("no global layer index" in m for m in said) == 1
    assert len(said) == 6          # a layer pattern: still not Llama's block
    nope = dataclasses.replace(windowed, sliding_window_layout=None,
                               rope_layout=spec.rope_layout)
    said = [str(r) for r in block_refusals(nope, asked)]
    assert not any("window mask" in m for m in said)
    assert sum("no global layer index" in m for m in said) == 1
    assert block_refusals(spec, EngineConfig(model=spec, **BASE)) == []
    assert ModelRunner(EngineConfig(model=spec, **BASE),
                       params=params).backends.experts_whole
    assert "w_gate" not in EngineConfig(model=spec).lora_target_shapes()


# -- embeddings: the block once, so the routed block has them -----------------------

#: Cosine of the served embedding with the reference's: 0.9991 to 0.9999
#: read here, 0.40 to 0.91 against the reference without the window (CPU
#: runs of a toy, PR 30; no device number).
EMBED_COSINE = 0.99


@pytest.mark.parametrize("pooling", ["last", "mean"])
def test_embeddings_agree_with_the_references_pooled_hidden_state(pooling):
    """embed_forward runs transformer_block (it held a copy of the dense
    block and refused this one): its pooled, normalised final hidden state
    against the plain reference's, rows of 40 and 33 tokens at window 8;
    the reference without the window is the control."""
    spec, params, tokens = toy(None)
    lens = np.asarray([SEQ, SEQ - 7], np.int32)
    got = np.asarray(jax.jit(lambda p: model.embed_forward(
        p, spec, tokens, lens, pooling=pooling))(params))
    assert got.shape == (2, spec.hidden_size) and got.dtype == np.float32
    np.testing.assert_allclose(np.linalg.norm(got, axis=-1), 1.0, atol=1e-5)

    def pooled(**switches):
        layer = ref.layer_of(spec, **switches)
        out = []
        with jax.default_matmul_precision("highest"):
            for row, n in zip(tokens, lens):
                x = plainref.plain(params["embed"])[row[:n]]
                for index in range(spec.num_layers):
                    x = layer(x, params["layers"], jnp.int32(index))
                h = plainref.rms_norm(x, params["final_norm"],
                                      float(spec.rms_norm_eps))
                v = h[-1] if pooling == "last" else h.mean(axis=0)
                out.append(v / jnp.linalg.norm(v))
        return np.asarray(jnp.stack(out), np.float32)

    cosine = np.sum(got * pooled(), axis=-1)
    control = np.sum(got * pooled(use_window=False), axis=-1)
    assert (cosine > EMBED_COSINE).all(), cosine
    assert (control < EMBED_COSINE).all(), control


# -- the two expert products agree ----------------------------------------------------

@pytest.mark.parametrize("quant", [None, "int8"])
def test_grouped_product_matches_the_masked_product(quant, monkeypatch):
    """Above MOE_DENSE_MAX_ROWS the (token, choice) pairs are sorted by
    expert and multiplied by their own experts only (the kernel of
    engine/experts.py, interpreted here); the result is the masked
    product's, to bfloat16's rounding, with skewed routing and experts
    nobody chose (empty groups)."""
    spec, params, _ = toy(quant)
    lp = jax.tree.map(lambda a: a[2], params["layers"])
    x = jax.random.normal(jax.random.key(1), (96, 64), jnp.bfloat16)
    rin = (jax.random.normal(jax.random.key(2), (96, 64)) * 0.2
           + jnp.linspace(-2, 2, 64)[None]).astype(jnp.bfloat16)
    router = jnp.einsum("th,he->te", rin, lp["moe_gate"],
                        preferred_element_type=jnp.float32)
    chosen = set(np.asarray(model.moe_route(router, spec)[1]).ravel())
    assert len(chosen) < spec.num_experts          # some group is empty
    outs = []
    monkeypatch.setattr(model, "MOE_DENSE_MAX_ROWS", 64)
    for product, record in (("grouped", WHOLE), ("masked", XLA)):
        assert model.expert_product(96, record) == product
        outs.append(np.asarray(jax.jit(lambda x, rin: model.ffn_block(
            x, lp, spec, router_in=rin, backends=record))(x, rin),
            np.float32))
    assert np.abs(outs[1]).mean() > 0.2
    np.testing.assert_allclose(outs[0], outs[1], atol=0.05)


@pytest.mark.parametrize("local", [True, False])
def test_the_product_is_chosen_by_rows_and_by_where_the_experts_are(
        local, monkeypatch):
    """One rule for every routed kind: where the caller says the experts
    are whole on one device (the runner: a mesh of one) a kernel reads the
    chosen experts alone, the walk over the touched experts up to
    MOE_DENSE_MAX_ROWS rows and the grouped product above it; the masked
    product wherever the expert axis may be partitioned. A Mixtral-style
    spec takes the same fork; no model's name decides. A decode step's rows
    (32) and a verify step's (64) take the walk (ROADMAP S9)."""
    assert model.MOE_DENSE_MAX_ROWS >= 64
    from dynamo_tpu.engine import experts
    calls = []
    for name, owner in (("_grouped_experts", model),
                        ("touched_product", experts)):
        monkeypatch.setattr(owner, name, (lambda real, name: lambda *a, **kw: (
            calls.append(name) or real(*a, **kw)))(getattr(owner, name),
                                                   name))
    mixtral = ModelSpec(vocab_size=64, hidden_size=32, intermediate_size=16,
                        num_layers=1, num_heads=2, num_kv_heads=1,
                        num_experts=4, num_experts_per_tok=2)
    lp = jax.tree.map(lambda a: a[0], model.init_params(
        mixtral, jax.random.key(0))["layers"])
    out = {}
    for rows, product in ((32, "touched"), (64, "touched"),
                          (model.MOE_DENSE_MAX_ROWS, "touched"),
                          (model.MOE_DENSE_MAX_ROWS + 8, "grouped")):
        x = jax.random.normal(jax.random.key(rows), (rows, 32), jnp.bfloat16)
        calls.clear()
        record = WHOLE if local else XLA
        out[rows] = model.ffn_block(x, lp, mixtral, backends=record)
        product = product if local else "masked"
        assert calls == {"masked": [], "grouped": ["_grouped_experts"],
                         "touched": ["touched_product"]}[product]
        assert model.expert_product(rows, record) == product
        masked = model.ffn_block(x, lp, mixtral)
        np.testing.assert_allclose(
            np.asarray(out[rows], np.float32),
            np.asarray(masked, np.float32), atol=0.03, rtol=0.02)
    base = dict(model=mixtral, page_size=PAGE, num_pages=32,
                max_pages_per_seq=16, max_num_seqs=2,
                prefill_buckets=(16, 32), attention_backend="xla")
    params = model.init_params(mixtral, jax.random.key(0))
    # The CPU interprets the kernel, as it does the attention kernels.
    on_cpu = ModelRunner(EngineConfig(**base), params=params).backends
    assert on_cpu.experts_whole and on_cpu.interpret
    assert not ModelRunner(EngineConfig(**base, tp=2),
                           params=params).backends.experts_whole


# -- the kernel's window: chunks wholly before it are not walked -------------------

@pytest.mark.parametrize("lo", [[0, 0, 0], [70, 0, 129], [31, 96, 199]])
def test_windowed_kernel_matches_the_gather_and_skips_dead_chunks(lo):
    """Histories of several chunks (32 tokens each at this page and head
    size), first visible token mid-chunk, on a chunk's edge, in the last
    chunk; a row without history; against the XLA gather with the same
    ``lo``. The third case's last row sees one token of history."""
    b, nh, nkv, d, page, pages = 3, 4, 2, 32, 4, 64
    keys = jax.random.split(jax.random.key(5), 5)
    q = jax.random.normal(keys[0], (b, nh, d), jnp.bfloat16)
    k_cache = jax.random.normal(keys[1], (2, nkv, b * pages + 1, page, d),
                                jnp.bfloat16)
    v_cache = jax.random.normal(keys[2], k_cache.shape, jnp.bfloat16)
    k_self = jax.random.normal(keys[3], (b, nkv, d), jnp.bfloat16)
    v_self = jax.random.normal(keys[4], (b, nkv, d), jnp.bfloat16)
    table = jnp.asarray(1 + np.arange(b * pages).reshape(b, pages), jnp.int32)
    hist = jnp.asarray([100, 0, 200], jnp.int32)
    lo = jnp.asarray(lo, jnp.int32)
    layer = jnp.int32(1)
    want = model.paged_decode_attention_xla(
        q, k_cache, v_cache, layer, table, hist, k_self, v_self, nh // nkv,
        lo=lo)
    got = paged_decode_attention_pallas(
        q, k_cache, v_cache, layer, table, hist, k_self, v_self,
        q_per_kv=nh // nkv, interpret=True, lo=lo)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=0.03)
    if not int(lo.max()):
        return
    # The window changes the answer (the test would pass on a kernel that
    # ignored lo otherwise) ...
    full = model.paged_decode_attention_xla(
        q, k_cache, v_cache, layer, table, hist, k_self, v_self, nh // nkv)
    assert float(jnp.max(jnp.abs(full.astype(jnp.float32)
                                 - want.astype(jnp.float32)))) > 0.05
    # ... and pages before the chunk that holds lo are never read: filled
    # with NaN, they would poison the accumulator through 0 * NaN.
    chunk = 32
    dead = np.zeros(k_cache.shape[2], bool)
    for row in range(b):
        first = int(lo[row]) // chunk * (chunk // page)
        dead[np.asarray(table[row, :first])] = True
    poisoned = jnp.where(jnp.asarray(dead)[None, None, :, None, None],
                         jnp.nan, k_cache)
    again = paged_decode_attention_pallas(
        q, poisoned, v_cache, layer, table, hist, k_self, v_self,
        q_per_kv=nh // nkv, interpret=True, lo=lo)
    assert np.isfinite(np.asarray(again, np.float32)).all()
    np.testing.assert_array_equal(np.asarray(again), np.asarray(got))


# -- through the engine: the counters ride the window's readback -------------------

@async_test(timeout=300)
async def test_the_engine_serves_the_block_and_counts_its_expert_load():
    from dynamo_tpu.engine.engine import TPUEngine
    from dynamo_tpu.llm.protocols import PreprocessedRequest
    from dynamo_tpu.runtime import flight
    from dynamo_tpu.runtime.context import Context
    spec, params, _ = toy(None)
    ring = flight.get_recorder()
    ring.thaw()
    ring.clear()
    engine = TPUEngine(EngineConfig(
        model=spec, page_size=PAGE, num_pages=128, max_pages_per_seq=32,
        max_num_seqs=4, prefill_buckets=(16, 32, 64), max_prefill_tokens=64,
        attention_backend="xla", decode_window=4), params=params)

    async def generate(prompt: int, n: int) -> list:
        req = PreprocessedRequest(model="m",
                                  token_ids=list(range(1, prompt + 1)))
        req.stop_conditions.max_tokens = n
        req.stop_conditions.ignore_eos = True
        got = []
        async for out in engine.generate(req, Context()):
            got.extend(out.get("token_ids", []))
        return got

    try:
        t_lo = time.monotonic()
        a, b = await asyncio.gather(generate(20, 21), generate(12, 13))
        assert (len(a), len(b)) == (21, 13)
        await asyncio.sleep(0.05)
        touched, load, n = (engine.counts_total[c] for c in (
            "moe_touched", "moe_load", "moe_layer_steps"))
        # Every counted (step, layer) pair had 1 or 2 live rows of 3
        # experts each: 3 to 6 distinct, the fullest holding 1 or 2 tokens
        # of a mean of rows * 3 / 8.
        assert n > 0 and n % spec.num_layers == 0
        assert 3 * n <= touched <= 6 * n
        assert 8 / 6 * n <= load <= 8 / 3 * n + 1e-6
        moe = engine.perf_status()["moe"]
        assert moe["layer_steps"] == n and moe["experts"] == 8
        assert 37.5 <= moe["experts_touched_pct"] <= 75.0
        assert 8 / 6 <= moe["load_max_over_mean"] <= 8 / 3 + 1e-6
        cols = ring.between(t_lo, time.monotonic())["columns"]
        assert cols["moe_layer_steps"].sum() == n
        assert cols["moe_touched"].sum() == pytest.approx(touched)
        assert cols["moe_load"].sum() == pytest.approx(load)
    finally:
        engine.stop()
