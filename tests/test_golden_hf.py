"""Golden parity vs HF transformers through the REAL checkpoint path
(VERDICT r2 #5): build tiny random Llama and Qwen2 checkpoints with
``save_pretrained``, parse their config.json with ModelSpec.from_hf_config,
load the safetensors with engine.weights.load_hf_weights, and compare
against the HF implementation running the same checkpoint in float32.

Comparisons are teacher-forced per step. Token agreement uses a margin
rule: our argmax must equal HF's chosen token, or HF's token must be
within a small logit margin of our max — bf16 (ours) vs fp32 (HF) can
legitimately flip near-ties with random weights, but a real mismatch
(wrong RoPE convention, transposed projection, bad GQA grouping) produces
large divergences that this catches immediately.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelSpec
from dynamo_tpu.engine.engine import TPUEngine
from dynamo_tpu.engine.backends import XLA
from dynamo_tpu.engine.model import decode_forward, prefill_forward
from dynamo_tpu.engine.weights import load_hf_weights
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.runtime.context import Context
from conftest import async_test

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

VOCAB = 256
MARGIN = 0.08  # bf16-vs-fp32 near-tie tolerance on logits


@pytest.fixture(scope="module")
def llama_dir(tmp_path_factory):
    cfg = transformers.LlamaConfig(
        vocab_size=VOCAB, hidden_size=128, intermediate_size=352,
        num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=4,
        max_position_embeddings=2048, rope_theta=10000.0, rms_norm_eps=1e-5,
        tie_word_embeddings=False, attention_bias=False)
    torch.manual_seed(0)
    model = transformers.LlamaForCausalLM(cfg).eval()
    d = tmp_path_factory.mktemp("tiny-llama")
    model.save_pretrained(d, safe_serialization=True)
    return str(d), model


@pytest.fixture(scope="module")
def qwen_dir(tmp_path_factory):
    cfg = transformers.Qwen2Config(
        vocab_size=VOCAB, hidden_size=128, intermediate_size=352,
        num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=4,
        max_position_embeddings=2048, rope_theta=10000.0, rms_norm_eps=1e-5,
        tie_word_embeddings=True)
    torch.manual_seed(1)
    model = transformers.Qwen2ForCausalLM(cfg).eval()
    d = tmp_path_factory.mktemp("tiny-qwen2")
    model.save_pretrained(d, safe_serialization=True)
    return str(d), model


def _our_stepwise_logits(spec, params, tokens):
    """Teacher-forced logits at every position: prefill the first 16
    tokens, then decode the rest one by one. Returns [len(tokens), V]
    logits where row i predicts token i+1."""
    page = 16
    n_prefill = 16
    assert len(tokens) > n_prefill
    num_pages = 32
    kv_shape = (spec.num_layers, spec.num_kv_heads, num_pages, page,
                spec.head_dim)
    k = jnp.zeros(kv_shape, jnp.bfloat16)
    v = jnp.zeros(kv_shape, jnp.bfloat16)
    tok = np.asarray([tokens[:n_prefill]], np.int32)
    pos = np.asarray([np.arange(n_prefill)], np.int32)
    ptab = np.asarray([[1]], np.int32)
    prefill = jax.jit(lambda p, k, v, t, po, pt, sl: prefill_forward(
        p, spec, k, v, t, po, pt, sl))
    logits, k, v = prefill(params, k, v, jnp.asarray(tok), jnp.asarray(pos),
                           jnp.asarray(ptab), jnp.asarray([n_prefill],
                                                          np.int32))
    out = [np.asarray(logits[0], np.float32)]
    decode = jax.jit(lambda p, k, v, t, po, pt, sl: decode_forward(
        p, spec, k, v, t, po, pt, sl, backends=XLA))
    page_table = np.zeros((1, 8), np.int32)
    page_table[0, :4] = [1, 2, 3, 4]
    for i in range(n_prefill, len(tokens)):
        logits, k, v = decode(
            params, k, v, jnp.asarray([tokens[i]], np.int32),
            jnp.asarray([i], np.int32), jnp.asarray(page_table),
            jnp.asarray([i + 1], np.int32))
        out.append(np.asarray(logits[0], np.float32))
    return np.stack(out)  # predicts tokens[n_prefill], tokens[n_prefill+1]...


def _check_against_hf(model_dir, hf_model, seed):
    spec = ModelSpec.from_hf_config(model_dir)
    assert spec.vocab_size == VOCAB and spec.num_kv_heads == 4
    params = load_hf_weights(spec, model_dir)

    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, VOCAB, size=16).tolist()
    with torch.no_grad():
        hf_out = hf_model.generate(
            torch.tensor([prompt]), max_new_tokens=16, do_sample=False)
    full = hf_out[0].tolist()
    assert len(full) == 32

    ours = _our_stepwise_logits(spec, params, full)
    # Row i predicts full[16 + i]; HF chose those tokens greedily in fp32.
    flips = 0
    for i in range(16):
        hf_tok = full[16 + i]
        row = ours[i]
        if int(np.argmax(row)) == hf_tok:
            continue
        gap = float(np.max(row) - row[hf_tok])
        assert gap < MARGIN, (
            f"step {i}: HF chose {hf_tok} but our logits prefer "
            f"{int(np.argmax(row))} by {gap:.3f} (beyond bf16 tolerance)")
        flips += 1
    # Near-ties must be the exception, not the rule.
    assert flips <= 4, f"{flips}/16 near-tie disagreements — suspicious"


def test_llama_checkpoint_golden(llama_dir):
    model_dir, hf_model = llama_dir
    for seed in (0, 1, 2):
        _check_against_hf(model_dir, hf_model, seed)


def test_qwen2_checkpoint_golden(qwen_dir):
    """Qwen2 exercises qkv_bias and tied embeddings in the loader."""
    model_dir, hf_model = qwen_dir
    spec = ModelSpec.from_hf_config(model_dir)
    assert spec.qkv_bias and spec.tie_word_embeddings
    for seed in (3, 4, 5):
        _check_against_hf(model_dir, hf_model, seed)


@async_test
async def test_engine_serves_hf_checkpoint(llama_dir):
    """Full TPUEngine on a real checkpoint directory (the worker's
    --model <dir> path): spec from config.json, weights from safetensors,
    greedy serving works end to end."""
    model_dir, hf_model = llama_dir
    spec = ModelSpec.from_hf_config(model_dir)
    params = load_hf_weights(spec, model_dir)
    cfg = EngineConfig(model=spec, page_size=16, num_pages=64,
                       max_pages_per_seq=16, max_num_seqs=4,
                       prefill_buckets=(32, 64), max_prefill_tokens=64,
                       attention_backend="xla")
    engine = TPUEngine(cfg, params=params)
    try:
        rng = np.random.default_rng(9)
        prompt = rng.integers(0, VOCAB, size=16).tolist()
        req = PreprocessedRequest(model="tiny-llama", token_ids=prompt)
        req.stop_conditions.max_tokens = 8
        req.stop_conditions.ignore_eos = True
        toks = []
        async for out in engine.generate(req, Context()):
            toks.extend(out.get("token_ids", []))
            if out.get("finish_reason"):
                break
        assert len(toks) == 8
        # Engine output must agree with HF greedy under the margin rule.
        with torch.no_grad():
            hf_out = hf_model.generate(torch.tensor([prompt]),
                                       max_new_tokens=8, do_sample=False)
        hf_toks = hf_out[0].tolist()[16:]
        agree = sum(a == b for a, b in zip(toks, hf_toks))
        assert agree >= 5, (toks, hf_toks)
    finally:
        engine.stop()
