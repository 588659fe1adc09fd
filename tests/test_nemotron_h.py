"""The Nemotron-H block (``nemotron_h``) at a toy size on the CPU, held to
benchmark/references/nemotron_h.py: layers of ONE mixer each (Mamba-2, an
expert layer of two-matrix relu2 experts, attention without a rotary
embedding), a recurrent state a SLOT beside the pages, a share of the
routed experts.

What is held: served logprobs against the reference's full forward after a
whole-prompt prefill, after a prefill in three chunks and for the rows of a
padded batch of unequal prompts; a slot's next request answers as a cold
run; a preempted row resumes token for token; a repeated prompt takes no
prefix hit and answers as the first time; the chunked scan equals the step
recurrence; dead rows keep their state; the four shares add up to the uncut
layer; the reader makes the catalog row's spec; each new refusal names what
is lacking.
"""

import asyncio
import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import async_test

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import manifest  # noqa: E402
from dynamo_tpu.engine import hybrid, model, perf, recurrence  # noqa: E402
from dynamo_tpu.engine.backends import Backends  # noqa: E402
from dynamo_tpu.engine.config import (EngineConfig, ModelSpec,  # noqa: E402
                                      NemotronHSpec, UnsupportedBlockError,
                                      block_refusals)
from dynamo_tpu.engine.engine import TPUEngine  # noqa: E402
from dynamo_tpu.engine.runner import (PK_CAP, PK_LOGPROB, PK_POS,  # noqa: E402
                                      PK_PREFIX, PK_SEQLEN, PK_TOPP,
                                      ModelRunner, PrefillSeq)
from dynamo_tpu.llm.protocols import PreprocessedRequest  # noqa: E402
from dynamo_tpu.runtime import flight  # noqa: E402
from dynamo_tpu.runtime.context import Context  # noqa: E402
from dynamo_tpu.runtime.metrics import MetricsRegistry  # noqa: E402

ref = manifest.load_module("references", "nemotron_h")

PAGE = 16
#: The catalog row's keys at a toy size: all three kinds, a * between an M
#: and an E, experts 4 to 7 of 16 held, a scan chunk of 8 tokens.
TOY = {
    "model_type": "nemotron_h", "attention_bias": False, "chunk_size": 8,
    "conv_kernel": 4, "expand": 2, "head_dim": 16, "hidden_size": 64,
    "hybrid_override_pattern": "MEM*EME", "intermediate_size": 32,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 8,
    "mamba_hidden_act": "silu", "mamba_num_heads": 8,
    "mamba_proj_bias": False, "max_position_embeddings": 2048,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 48,
    "n_group": 1, "n_groups": 2, "n_routed_experts": 4,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts_per_tok": 2,
    "num_hidden_layers": 7, "num_key_value_heads": 2,
    "routed_scaling_factor": 2.5, "rope_theta": 10000,
    "sliding_window": None, "ssm_state_size": 16,
    "tie_word_embeddings": False, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "vocab_size": 48,
    "expert_parallel": {"routed_experts": 16, "first_expert": 4},
}


def read_spec(cfg: dict) -> ModelSpec:
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "config.json")
        with open(path, "w", encoding="utf-8") as fh:
            json.dump({k: v for k, v in cfg.items()
                       if not (k == "expert_parallel" and v is None)}, fh)
        return dataclasses.replace(ModelSpec.from_hf_config(path), name="nem")


def seeded_params(spec, seed: int):
    """init_params, then what it draws as ones drawn from the seed: A, D
    (a head's vectors end in a 1) so that heads differ in how they forget;
    and a decisive router (with logits of unit size the choice of 2 of 16
    flips between two roundings of one state every few tokens)."""
    params = model.init_params(spec, jax.random.key(seed))
    key = jax.random.key(seed + 100)
    layers = params["layers"]
    for i, name in enumerate(("ssm_a_log", "ssm_d")):
        layers[name] = (0.5 * jax.random.normal(
            jax.random.fold_in(key, i), layers[name].shape)).astype(
            jnp.bfloat16)
    layers["moe_gate"] = layers["moe_gate"] * 8.0
    return params


SPEC = read_spec(TOY)
PARAMS = seeded_params(SPEC, 11)


def config(**kw) -> EngineConfig:
    defaults = dict(model=SPEC, page_size=PAGE, num_pages=128,
                    max_pages_per_seq=16, max_num_seqs=4,
                    prefill_buckets=(32, 64, 128), max_prefill_tokens=64,
                    attention_backend="xla", decode_window=4,
                    pipeline_depth=2)
    defaults.update(kw)
    return EngineConfig(**defaults)


def prompt_of(n: int, seed: int) -> list[int]:
    return np.random.default_rng(seed).integers(
        0, SPEC.vocab_size, size=n).tolist()


async def collect(engine, prompt, max_tokens, logprobs=None):
    req = PreprocessedRequest(model="m", token_ids=list(prompt))
    req.stop_conditions.max_tokens = max_tokens
    req.stop_conditions.ignore_eos = True
    if logprobs is not None:
        req.sampling_options.logprobs = logprobs
    toks, lps, finish = [], [], None
    async for out in engine.generate(req, Context()):
        toks.extend(out.get("token_ids", []))
        lps.extend(out.get("log_probs") or [])
        if out.get("finish_reason"):
            finish = out["finish_reason"]
            break
    return toks, lps, finish


def close(a, b) -> bool:
    """Two lists of logprobs of the same tokens agree: the median within
    0.02 nat and nine in ten within 0.1, one of a short list (a router's
    choice that flips between two roundings of one state moves a token by
    tenths of a nat; a wrong state moves every token)."""
    d = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
    return bool(np.median(d) < 0.02
                and (d > 0.1).sum() <= max(1, len(d) // 10))


def router_margins(params, spec, tokens) -> np.ndarray:
    """[len(tokens)]: how far, in the reference, the LAST expert chosen
    stands above the first one left out, the smallest over the expert
    layers: where it is a hundredth, two correct programs choose apart."""
    from benchmark.lib.reference import rms_norm
    layer = ref.layer_of(spec)
    layers, k = params["layers"], spec.num_experts_per_tok
    least = np.full(len(tokens), np.inf)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][np.asarray(tokens)].astype(jnp.float32)
        for i, kind in enumerate(spec.layer_pattern):
            if kind == "E":
                row = spec.layer_pattern[:i].count("E")
                u = rms_norm(x, layers["mixer_norm"][i], spec.rms_norm_eps)
                score = jax.nn.sigmoid(
                    u @ layers["moe_gate"][row].astype(jnp.float32)) \
                    + layers["moe_bias"][row][:, 0].astype(jnp.float32)
                ranked = np.sort(np.asarray(score), axis=-1)[:, ::-1]
                least = np.minimum(least, ranked[:, k - 1] - ranked[:, k])
            x = layer(x, layers, i)
    return least


def close_up_to_a_tie(served, prompt, generated, params=None, spec=None
                      ) -> bool:
    """``close`` to the reference's logprobs; or close up to the first
    token that is a tenth of a nat off, and that token (or one of the two
    before it) stands where the reference's router chose by less than
    0.02: the program's choice there is another, what it adds to the
    stream is another expert's, and every later token of this small model
    carries it. A wrong state, page or position is off where no choice is
    close."""
    params, spec = params or PARAMS, spec or SPEC
    want = ref.reference_logprobs(params, spec, prompt, generated)
    if close(served, want):
        return True
    d = np.abs(np.asarray(served, np.float64) - np.asarray(want, np.float64))
    at = int(np.argmax(d > 0.1))
    tokens = list(prompt) + list(generated[:-1])
    where = len(prompt) - 1 + at        # the position that predicts ``at``
    margins = router_margins(params, spec, tokens)[max(where - 2, 0):
                                                   where + 1]
    return bool((at < 2 or close(served[:at], want[:at]))
                and margins.min() < 0.02)


def same_up_to_a_tie(got, want, prompt) -> bool:
    """Two greedy streams of one prompt are the same, or part where the
    reference holds the two tokens within 0.08 of each other (two correct
    programs round one state differently, and the larger of two nearly
    equal logits changes sides; past that token the streams are of
    different texts). A row that resumed from a wrong state parts at a
    token the reference tells apart."""
    if list(got) == list(want):
        return True
    at = next(i for i, (a, b) in enumerate(zip(got, want)) if a != b)
    logits = reference_logits(PARAMS, SPEC, list(prompt) + list(want[:at]))
    return bool(abs(float(logits[-1, got[at]] - logits[-1, want[at]]))
                < 0.08)


def reference_logits(params, spec, tokens, **switches):
    """The reference's logits [len(tokens), vocab], float32."""
    from benchmark.lib.reference import rms_norm
    layer = ref.layer_of(spec, **switches)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][np.asarray(tokens)].astype(jnp.float32)
        for i in range(spec.num_layers):
            x = layer(x, params["layers"], i)
        h = rms_norm(x, params["final_norm"], spec.rms_norm_eps)
        return h @ params["lm_head"].astype(jnp.float32)


# -- the reader ----------------------------------------------------------------

def catalog_row() -> dict:
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path, encoding="utf-8") as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    return next(r for r in rows
                if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")


def test_the_reader_makes_the_catalog_row_s_spec():
    spec = read_spec(catalog_row()["config"])
    assert isinstance(spec, NemotronHSpec)
    pairs = hybrid.groups_of(spec)
    assert (spec.num_layers, spec.ssm_layers, spec.expert_layers,
            spec.pool_layers) == (52, 23, 23, 6)
    assert [a for a in pairs.attn_layer if a >= 0] == [5, 12, 19, 26, 33, 42]
    assert [p for p, a in enumerate(pairs.attn_index) if a >= 0] \
        == [2, 5, 8, 11, 14, 18]
    assert spec.kv_entry == (2, (128, 128))
    assert spec.kv_bytes_per_token() == 6144
    assert spec.ssm_state_bytes_per_row == 23 * (64 * 64 * 128 * 4
                                                 + 3 * 6144 * 2)
    assert (spec.router_width, spec.num_experts, spec.num_experts_per_tok,
            spec.expert_size, spec.shared_intermediate_size) \
        == (128, 128, 6, 1856, 3712)
    assert spec.ffn_act == "relu2" and spec.moe_select_bias
    shapes = model.param_shapes(spec)["layers"]
    assert "moe_w_gate" not in shapes and "shared_w_gate" not in shapes
    assert shapes["ssm_w_in"] == (23, 2688, 4096 + 6144)
    assert shapes["ssm_w_dt"] == (23, 2688, 64)
    assert shapes["ssm_conv_w"] == (23, 4, 6144)
    assert shapes["shared_w_up"] == (23, 1, 2688, 3712)
    assert shapes["wk"] == (6, 2688, 256)
    assert shapes["mixer_norm"] == (52, 2688)
    assert spec.num_params() == 31_577_940_288
    # The chip's share: 32 of the 128 experts.
    cut = read_spec({**catalog_row()["config"], "n_routed_experts": 32,
                     "expert_parallel": {"routed_experts": 128,
                                         "first_expert": 0}})
    assert abs(cut.num_params() / 9_546.7e6 - 1) < 1e-3


@pytest.mark.parametrize("pattern", ["MEME*", "EMEM", "M**EME", "MEMM"])
def test_a_pattern_the_scan_is_not_written_for_is_refused(pattern):
    with pytest.raises(UnsupportedBlockError, match="pairs"):
        read_spec({**TOY, "hybrid_override_pattern": pattern,
                   "num_hidden_layers": len(pattern)})


@pytest.mark.parametrize("key,value,names", [
    ("mlp_hidden_act", "silu", "relu2"),
    ("use_conv_bias", False, "bias"),
    ("n_shared_experts", 2, "shared"),
    ("time_step_limit", [0.0, 1.0], "clamp"),
    ("scoring_func", "softmax", "sigmoid"),
])
def test_the_reader_refuses_what_is_not_written_down(key, value, names):
    with pytest.raises(UnsupportedBlockError, match=names):
        read_spec({**TOY, key: value})


# -- refusals ------------------------------------------------------------------

@pytest.mark.parametrize("asked,names", [
    (dict(spec_decode="ngram", spec_k=2), "rejected draft"),
    (dict(spec_decode="mtp", spec_k=1), "rejected draft"),
    (dict(host_cache_pages=8), "recurrent state"),
    (dict(tp=2), "partitioning rule"),
    (dict(ring_attention=True, sp=2), "hand-over"),
    (dict(pp_microbatch=True, pp=2), "recurrent state"),
    (dict(max_adapters=2), "have none of them"),
    (dict(quant_kv="int8"), "bfloat16 pool"),
])
def test_each_engine_path_that_lacks_the_state_is_refused(asked, names):
    refusals = block_refusals(SPEC, config(**asked))
    assert any(names in str(r) for r in refusals), [str(r) for r in refusals]
    with pytest.raises(UnsupportedBlockError):
        ModelRunner(config(**asked), params=PARAMS)


@pytest.mark.parametrize("asked,names", [
    (dict(kv_transfer=True), "has no parcel"),
    (dict(checkpoint=True), "tensor-name map"),
    (dict(embeddings=True), "token rows alone"),
])
def test_a_parcel_a_checkpoint_and_embeddings_are_refused(asked, names):
    refusals = block_refusals(SPEC, **asked)
    assert any(names in str(r) for r in refusals), [str(r) for r in refusals]


def test_the_other_blocks_keep_their_refusals_and_fields():
    dense = ModelSpec()
    assert not dense.recurrent and dense.ssm_state_bytes_per_row == 0
    assert dense.pool_layers == dense.num_layers
    assert block_refusals(dense, EngineConfig(model=dense)) == []


# -- the recurrence --------------------------------------------------------------

def _mixer_inputs(rows: int, tokens: int, seed: int):
    layers = PARAMS["layers"]
    lp = {k: v[1] for k, v in layers.items() if k.startswith("ssm_")}
    h = jax.random.normal(jax.random.key(seed),
                          (rows, tokens, SPEC.hidden_size)).astype(
        jnp.bfloat16)
    s_shape, c_shape = SPEC.ssm_state_shapes
    return lp, h, jnp.zeros((rows, *s_shape), jnp.float32), jnp.zeros(
        (rows, *c_shape), jnp.bfloat16)


@pytest.mark.parametrize("tokens", [5, 8, 21, 32])
def test_the_chunked_scan_equals_the_step_recurrence(tokens):
    """Chunks of 8 as matrix products against one token at a time: the
    outputs, the state and the convolution's inputs after the last token;
    a row of a padded batch stops at its own last token."""
    lp, h, state, conv = _mixer_inputs(2, tokens, 3)
    lens = jnp.asarray([tokens, max(tokens - 3, 1)])
    valid = jnp.arange(tokens)[None, :] < lens[:, None]
    out, s_end, c_end = hybrid.ssm_chunked(h, lp, SPEC, state, conv, valid,
                                           lens)
    outs = []
    planes = jnp.moveaxis(conv, 0, 1)       # a step's are taps-major
    for t in range(tokens):
        o, state, planes = hybrid.ssm_step(h[:, t], lp, SPEC, state, planes,
                                           valid[:, t])
        outs.append(o)
    conv = jnp.moveaxis(planes, 0, 1)
    steps = jnp.stack(outs, axis=1).astype(jnp.float32)
    got = out.astype(jnp.float32)
    scale = float(jnp.abs(steps).max())
    for row, n in enumerate(np.asarray(lens)):
        np.testing.assert_allclose(got[row, :n], steps[row, :n],
                                   atol=0.03 * scale)
    np.testing.assert_allclose(s_end, state, atol=0.02 * float(
        jnp.abs(state).max()))
    np.testing.assert_array_equal(np.asarray(c_end, np.float32),
                                  np.asarray(conv, np.float32))


def test_a_chunk_continues_from_the_state_it_is_given():
    lp, h, state, conv = _mixer_inputs(1, 24, 4)
    lens, valid = jnp.asarray([24]), jnp.ones((1, 24), bool)
    whole, s_whole, c_whole = hybrid.ssm_chunked(h, lp, SPEC, state, conv,
                                                 valid, lens)
    first, s_mid, c_mid = hybrid.ssm_chunked(
        h[:, :16], lp, SPEC, state, conv, valid[:, :16], jnp.asarray([16]))
    rest, s_end, c_end = hybrid.ssm_chunked(
        h[:, 16:], lp, SPEC, s_mid, c_mid, valid[:, 16:], jnp.asarray([8]))
    scale = float(jnp.abs(whole.astype(jnp.float32)).max())
    np.testing.assert_allclose(
        jnp.concatenate([first, rest], axis=1).astype(jnp.float32),
        whole.astype(jnp.float32), atol=0.03 * scale)
    np.testing.assert_allclose(s_end, s_whole, atol=0.02 * float(
        jnp.abs(s_whole).max()))
    np.testing.assert_array_equal(np.asarray(c_end, np.float32),
                                  np.asarray(c_whole, np.float32))


def test_the_recurrence_is_a_first_order_part_of_the_output():
    """Under init_params' law the term S_t C_t is not drowned by D x_t, so
    a wrong scan shows: the reference without it (``ssm=false``) and with a
    history-free convolution (``conv=false``) moves the logits by more than
    the program differs from the reference."""
    tokens = prompt_of(40, 5)
    full = reference_logits(PARAMS, SPEC, tokens)
    for switch in ({"ssm": False}, {"conv": False}, {"gate": False}):
        wrong = reference_logits(PARAMS, SPEC, tokens, **switch)
        assert float(jnp.abs(wrong - full).mean()) > 0.05, switch


# -- the kernel of the decode step ------------------------------------------------

#: seq_lens0, positions0 and cap of six slots over a four-step window: which
#: rows a step finds live is (seq_lens0 > 0) & (positions < cap), as the
#: window program computes it, and a live row's position moves a step on.
WALKS = {
    "every row live": ([9] * 6, [8] * 6, [16] * 6),
    "no row live": ([0] * 6, [0] * 6, [16] * 6),
    "live rows scattered, the last slot among them":
        ([0, 9, 0, 0, 9, 9], [8] * 6, [16] * 6),
    "one live row": ([0, 0, 9, 0, 0, 0], [8] * 6, [16] * 6),
    "a row freezes at its cap in the second step":
        ([9, 0, 15, 9, 0, 0], [8, 0, 15, 8, 0, 0], [32, 16, 16, 32, 16, 16]),
}


def _bits(a) -> np.ndarray:
    return np.asarray(a).view(np.int32)


@pytest.mark.parametrize("walk", list(WALKS))
def test_the_kernel_updates_the_live_rows_and_touches_no_other(walk):
    """engine/recurrence.py through the Pallas interpreter against
    ``hybrid.ssm_step`` at the toy's widths, four steps of one layer of a
    stack of three: a live row's output and new state are the
    definition's to float32 rounding; a slot that is dead (NaN in its state
    from the start: whoever reads or writes it shows), a row from the step
    it freezes at on, and the other layers keep their state BITWISE."""
    seq_lens0, positions, cap = (np.asarray(a) for a in WALKS[walk])
    rows, layer = len(cap), 1
    lp, h, _, conv = _mixer_inputs(rows, 4, 7)
    conv = jnp.moveaxis(conv, 0, 1)         # a step's are taps-major
    s_shape, _ = SPEC.ssm_state_shapes
    states = 0.5 * jax.random.normal(jax.random.key(8),
                                     (3, rows, *s_shape), jnp.float32)
    ever = seq_lens0 > 0
    states = jnp.where(ever[None, :, None, None, None], states, jnp.nan)
    want, conv_want = states[layer], conv
    step = jax.jit(lambda *a: hybrid.ssm_step_live(
        *a[:2], SPEC, *a[2:], interpret=True))
    define = jax.jit(lambda *a: hybrid.ssm_step(*a[:2], SPEC, *a[2:]))
    token = jax.jit(lambda *a: hybrid._token(*a[:2], SPEC, *a[2:]))
    for t in range(4):
        live = jnp.asarray(ever & (positions < cap))
        on, held = np.asarray(live), states
        walked = hybrid.live_walk(live)
        # The kernel alone: y = S_t C_t of the definition's new state.
        _, x, bb, cc, dt, da, _ = token(h[:, t], lp, conv, live)
        _, y = recurrence.state_step(
            states, layer, *walked, jnp.exp(da),
            (dt.reshape(*x.shape[:3], 1) * x).reshape(rows, dt.shape[1], -1),
            bb, cc, interpret=True)
        out_want, want, conv_want = define(h[:, t], lp, want, conv_want,
                                           live)
        y_want = jnp.sum(want.reshape(*x.shape, -1)
                         * cc[:, :, None, None, :], axis=-1).reshape(y.shape)
        np.testing.assert_allclose(np.asarray(y)[on], np.asarray(y_want)[on],
                                   rtol=1e-5, atol=1e-5)
        assert not np.asarray(y)[~on].any()
        # The mixer around it: out (bfloat16), the state and the inputs.
        out, states, conv = step(h[:, t], lp, states, jnp.int32(layer),
                                 conv, live, walked)
        np.testing.assert_allclose(
            np.asarray(out, np.float32)[on],
            np.asarray(out_want, np.float32)[on],
            atol=0.01 * float(jnp.abs(out_want.astype(jnp.float32)[on]).max()
                              if on.any() else 1.0))
        np.testing.assert_allclose(np.asarray(states[layer])[on],
                                   np.asarray(want)[on], rtol=2e-6, atol=1e-6)
        np.testing.assert_array_equal(_bits(states[layer])[~on],
                                      _bits(held[layer])[~on])
        np.testing.assert_array_equal(_bits(states[::2]), _bits(held[::2]))
        np.testing.assert_array_equal(np.asarray(conv, np.float32),
                                      np.asarray(conv_want, np.float32))
        positions = positions + on
    if "freezes" in walk:
        assert not on[2] and np.isfinite(np.asarray(states[layer, 2])).all()


#: Which of six slots a step finds live.
MASKS = {"no row live": [False] * 6, "every row live": [True] * 6,
         "holes": [False, True, False, False, True, True]}


def _bits16(a) -> np.ndarray:
    return np.asarray(a).view(np.uint16)


@pytest.mark.parametrize("mask", list(MASKS))
def test_a_token_s_convolution_over_planes_is_the_sum_over_a_row_s_inputs(
        mask):
    """``hybrid.conv_token`` at the toy's widths (x | B | C, a bias behind
    the sum), two tokens over a layer's taps-major planes [K - 1, B, C]:
    the float32 sum is, to rounding, the definition's until PR 53 (a row's
    K inputs first, summed over that axis), and so is what ``_token_of``
    makes of it; a live row's planes move one tap on with the token's
    inputs the last; a dead row's come back BITWISE."""
    on = np.asarray(MASKS[mask])
    rows = len(on)
    lp, h, _, _ = _mixer_inputs(rows, 2, 11)
    taps_n, chan = SPEC.ssm_state_shapes[1]
    planes = jax.random.normal(jax.random.key(12), (taps_n, rows, chan)
                               ).astype(jnp.bfloat16)
    live, taps = jnp.asarray(on), lp["ssm_conv_w"]
    for t in range(2):
        parts = hybrid._project(h[:, t], lp, SPEC)
        held = planes
        acc, planes = hybrid.conv_token(held, parts[1], taps, live)
        full = jnp.concatenate([jnp.moveaxis(held, 0, 1),
                                parts[1][:, None].astype(jnp.bfloat16)],
                               axis=1)
        old = jnp.sum(full.astype(jnp.float32) * taps.astype(jnp.float32),
                      axis=1)
        np.testing.assert_allclose(acc, old, rtol=1e-5, atol=1e-5)
        for got, was in zip(hybrid._token_of(parts, lp, SPEC, acc, live),
                            hybrid._token_of(parts, lp, SPEC, old, live)):
            np.testing.assert_allclose(got, was, rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(
            _bits16(planes[-1])[on],
            _bits16(parts[1].astype(jnp.bfloat16))[on])
        np.testing.assert_array_equal(_bits16(planes[:-1])[:, on],
                                      _bits16(held[1:])[:, on])
        np.testing.assert_array_equal(_bits16(planes)[:, ~on],
                                      _bits16(held)[:, ~on])


def test_the_window_step_walks_the_rows_it_counts():
    """hybrid.window_step with the kernel (interpreted) against XLA's
    ``ssm_step``: the live rows' logits, both state arrays and the count
    of live rows agree, and the count is the one the kernel walked to."""
    rows, window, pages = 4, 4, 8
    nkv, d = SPEC.num_kv_heads, SPEC.head_dim
    pool = jnp.zeros((SPEC.pool_layers, nkv, pages, PAGE, d), jnp.bfloat16)
    buf = jnp.zeros((SPEC.pool_layers, nkv, rows, window, d), jnp.bfloat16)
    s_shape, c_shape = SPEC.ssm_state_shapes
    state = (jax.random.normal(jax.random.key(1),
                               (SPEC.ssm_layers, rows, *s_shape)),
             jnp.zeros(SPEC.conv_state_shape(rows), jnp.bfloat16))
    live = jnp.asarray([True, False, True, True])
    args = (PARAMS, SPEC, pool, pool, buf, buf, jnp.int32(0),
            jnp.asarray([3, 0, 5, 7]), jnp.zeros((rows, 2), jnp.int32),
            jnp.zeros(rows, jnp.int32), state, live)
    want = hybrid.window_step(*args)
    got = hybrid.window_step(*args, backends=Backends(
        ssm="kernel", interpret=True))
    on = np.asarray(live)
    np.testing.assert_allclose(np.asarray(got[0], np.float32)[on],
                               np.asarray(want[0], np.float32)[on],
                               atol=0.02 * float(jnp.abs(want[0]).max()))
    for a, b in zip(got[3], want[3]):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=2e-6,
                                   atol=1e-6)
    assert float(got[4]["ssm"][0, 0]) == float(want[4]["ssm"][0, 0]) == 3.0


# -- the runner ------------------------------------------------------------------

def _window(runner, rows: dict, steps: int):
    """One window over ``rows`` {slot: (position, pages)}; returns the
    tokens and logprobs [steps, slots]."""
    packed = np.zeros((runner.config.max_num_seqs, PK_PREFIX + 8), np.int32)
    packed[:, PK_TOPP] = np.float32(1.0).view(np.int32)
    for slot, (pos, pages) in rows.items():
        packed[slot, PK_POS] = pos
        packed[slot, PK_SEQLEN] = pos + 1
        packed[slot, PK_CAP] = len(pages) * PAGE
        packed[slot, PK_LOGPROB] = 1
        packed[slot, PK_PREFIX:PK_PREFIX + len(pages)] = pages
    toks, lps, _, _, counted = runner.decode_window(packed, steps)
    return np.asarray(toks), np.asarray(lps), counted


def test_a_padded_batch_of_unequal_prompts_and_its_windows():
    """Three prompts of 9, 21 and 30 tokens in one bucket of 32, then two
    windows over their slots with a dead slot between them: each row's
    logprobs are the reference's for its own tokens, the dead slot's state
    stays as it was, and the window counts its live rows."""
    runner = ModelRunner(config(), params=PARAMS)
    prompts = [prompt_of(n, 30 + n) for n in (9, 21, 30)]
    slots, pages = [0, 1, 3], [[1, 2], [3, 4], [5, 6, 7]]
    seqs = [PrefillSeq(tokens=np.asarray(p, np.int32), start_pos=0,
                       chunk_pages=np.asarray(pg[:2], np.int32),
                       hist_pages=None, sampling=(0.0, 0, 1.0))
            for p, pg in zip(prompts, pages)]
    # The dead slot holds something a wrong program would disturb.
    runner.ssm_state = runner.ssm_state.at[:, 2].set(7.0)
    runner.conv_state = runner.conv_state.at[:, :, 2].set(3.0)
    first = np.asarray(runner.prefill_batch(seqs, slots=slots)["tokens"])
    logits = np.asarray(runner.last_prefill_logits, np.float32)
    for row, prompt in enumerate(prompts):
        want = reference_logits(PARAMS, SPEC, prompt)[-1]
        assert float(np.abs(logits[row] - want).max()) < 0.08 * float(
            np.abs(want).max())
    rows = {s: (len(p), pg) for s, p, pg in zip(slots, prompts, pages)}
    toks, lps = [], []
    for w in range(2):
        t, lp, counted = _window(
            runner, {s: (pos + 4 * w, pg) for s, (pos, pg) in rows.items()},
            4)
        toks.append(t)
        lps.append(lp)
        assert float(np.asarray(counted["ssm"])[0]) == 12.0
    toks, lps = np.concatenate(toks), np.concatenate(lps)
    for row, (slot, prompt) in enumerate(zip(slots, prompts)):
        # The prefill's first token is the last of what the windows read.
        assert close_up_to_a_tie(
            lps[:, slot], prompt + [int(first[row])],
            [int(t) for t in toks[:, slot]]), (slot, lps[:, slot])
    assert float(jnp.abs(runner.ssm_state[:, 2] - 7.0).max()) == 0.0
    assert float(jnp.abs(runner.conv_state[:, :, 2].astype(jnp.float32)
                         - 3.0).max()) == 0.0
    memory = runner.memory_breakdown()
    assert memory["ssm_state_bytes"] == 4 * SPEC.ssm_state_bytes_per_row \
        == runner.ssm_state.nbytes + runner.conv_state.nbytes


def test_the_windows_leave_in_a_slot_s_planes_what_a_prefill_leaves():
    """A prompt's chunk, then two windows (under XLA's update and under the
    kernel's, interpreted): the carried inputs a row holds in its slot of
    the taps-major stack are what a prefill of the same 29 tokens writes
    there, as far as bfloat16 rounding lets two paths agree (values of 0.8:
    half of them equal, one in a hundred 0.024 apart; the planes in another
    order 0.38), under either update the same, and a slot nobody served
    keeps its own."""
    got = {}
    for who in ("xla", "kernel"):
        runner = ModelRunner(config(), params=PARAMS)
        runner.backends = dataclasses.replace(runner.backends, ssm=who)
        assert runner.conv_state.shape == SPEC.conv_state_shape(
            runner.config.max_num_seqs)
        runner.conv_state = runner.conv_state.at[:, :, 0].set(3.0)
        prompt = prompt_of(21, 51)
        seq = PrefillSeq(tokens=np.asarray(prompt, np.int32), start_pos=0,
                         chunk_pages=np.asarray([1, 2], np.int32),
                         hist_pages=None, sampling=(0.0, 0, 1.0))
        first = int(np.asarray(
            runner.prefill_batch([seq], slots=[2])["tokens"])[0])
        toks, lps = zip(*(_window(runner, {2: (len(prompt) + 4 * w,
                                               [1, 2, 3])}, 4)[:2]
                          for w in range(2)))
        got[who] = (np.concatenate(toks)[:, 2], np.concatenate(lps)[:, 2],
                    np.asarray(runner.conv_state, np.float32), first)
    np.testing.assert_array_equal(got["xla"][0], got["kernel"][0])
    np.testing.assert_allclose(got["xla"][1], got["kernel"][1], atol=2e-3)
    np.testing.assert_allclose(got["xla"][2], got["kernel"][2], atol=0.02)
    assert (got["kernel"][2][:, :, 0] == 3.0).all()
    # The same 29 tokens as ONE prompt: the chunk's last K - 1 inputs.
    toks, _, planes, first = got["xla"]
    whole = prompt_of(21, 51) + [first] + [int(t) for t in toks[:7]]
    runner = ModelRunner(config(), params=PARAMS)
    runner.prefill_batch([PrefillSeq(
        tokens=np.asarray(whole, np.int32), start_pos=0,
        chunk_pages=np.asarray([1, 2], np.int32), hist_pages=None,
        sampling=(0.0, 0, 1.0))], slots=[2])
    want = np.asarray(runner.conv_state, np.float32)[:, :, 2]
    apart = np.abs(planes[:, :, 2] - want)
    assert np.abs(want).mean() > 0.3
    assert np.median(apart) < 0.01 and np.percentile(apart, 99) < 0.1


def test_a_long_batch_s_rows_go_to_their_own_experts():
    """Three prompts in a bucket of 64 are 256 rows a batch of four, over
    model.MOE_DENSE_MAX_ROWS: the prefill program is labelled ``grouped``
    (the kernel of engine/experts.py, interpreted here, over two-matrix
    relu2 experts 32 wide, experts 4 to 7 of a router over 16), its logits
    are the masked product's, both state arrays lie as the masked run
    leaves them, the pairs are counted, and the window walks the touched
    experts (``touched``; ``masked`` where the record says the experts may
    be partitioned)."""
    assert SPEC.expert_size % 128 and SPEC.holds_share
    prompts = [prompt_of(n, 70 + n) for n in (40, 57, 64)]
    slots, pages = [0, 2, 3], [[1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 12]]
    seqs = [PrefillSeq(tokens=np.asarray(p, np.int32), start_pos=0,
                       chunk_pages=np.asarray(pg, np.int32),
                       hist_pages=None, sampling=(0.0, 0, 1.0))
            for p, pg in zip(prompts, pages)]
    got = {}
    for product in ("grouped", "masked"):
        # (One program holds the whole group: 4 x 64 rows.)
        runner = ModelRunner(config(max_prefill_tokens=256), params=PARAMS)
        assert runner.backends.experts_whole and runner.backends.interpret
        if product == "masked":
            runner.backends = dataclasses.replace(runner.backends,
                                                  experts_whole=False)
        runner.prefill_batch(seqs, slots=slots)
        fn, = runner._prefill_cache.values()
        assert fn._labels["expert_product"] == product
        assert runner.moe_grouped_pairs == (
            256 * SPEC.num_experts_per_tok if product == "grouped" else 0)
        assert runner._get_window(4, 4)._labels["expert_product"] == (
            "touched" if product == "grouped" else "masked")
        got[product] = [np.asarray(a, np.float32) for a in (
            runner.last_prefill_logits, runner.ssm_state, runner.conv_state)]
    for name, a, b in zip(("logits", "state", "conv"), *got.values()):
        assert np.abs(b).max() > 0.1, name
        assert np.abs(a - b).max() < 0.03 * np.abs(b).max(), name
    np.testing.assert_array_equal(got["grouped"][1][:, 1], 0.0)


def test_an_inert_row_writes_no_state():
    """A warm-up's row (no slot) and a padding row leave every slot as it
    was; the pool is sized after the state arrays."""
    runner = ModelRunner(config(), params=PARAMS)
    runner.ssm_state = runner.ssm_state + 5.0
    seq = PrefillSeq(tokens=np.zeros(20, np.int32), start_pos=0,
                     chunk_pages=np.zeros(1, np.int32), hist_pages=None,
                     sampling=(0.0, 0, 1.0))
    runner.prefill_batch([seq] * 3, fetch=False)
    assert float(jnp.abs(runner.ssm_state - 5.0).max()) == 0.0
    sized = dataclasses.replace(config(), num_pages=None)
    probe = object.__new__(ModelRunner)
    probe.config, probe.spec, probe.quant_kv = sized, SPEC, None
    probe._sized_pages(jax.devices()[0])
    wide = dataclasses.replace(sized, max_num_seqs=4096)
    probe2 = object.__new__(ModelRunner)
    probe2.config, probe2.spec, probe2.quant_kv = wide, SPEC, None
    probe2._sized_pages(jax.devices()[0])
    assert probe2.num_pages < probe.num_pages


# -- the engine ------------------------------------------------------------------

@async_test
async def test_the_engine_serves_what_the_reference_computes():
    """Whole-prompt prefill then decode windows; a prompt past the chunk
    budget in three chunks (the state carried across chunk borders, the
    attention layers over history pages); counters and labels."""
    ring = flight.get_recorder()
    ring.thaw()
    ring.clear()
    t_lo = time.monotonic()
    engine = TPUEngine(config(max_prefill_tokens=32), params=PARAMS)
    engine.start()
    try:
        # (The 66 tokens were seed 4's until PR 56: that stream stands at
        # ``close``'s limit under either expert product, 0.018 over the six
        # tokens ahead of a tie of 1.6 nat under the masked one and 0.0215
        # over the eight ahead of one of 0.11 under the walk; of seeds 4 to
        # 15 ten are ``close`` outright under both, this one at a median of
        # 0.008 | 0.005 and 0.03 at worst. CPU, PR 56.)
        for seed, n, cap in ((1, 19, 21), (2, 31, 14), (3, 80, 18),
                             (6, 66, 12)):
            prompt = prompt_of(n, seed)
            got, lps, finish = await collect(engine, prompt, cap, logprobs=1)
            assert len(got) == cap and finish == "length"
            assert close_up_to_a_tie(lps, prompt, got), (n, lps)
        assert engine.chunk_dispatch_count >= 6     # 80 and 66 in threes
        assert engine.prefix_hit_blocks == 0
        assert engine.allocator.stats()["reuse_hit_blocks"] == 0
        status = engine.perf_status()
        assert status["ssm"] == {
            "layers": 3, "kind": "mamba2", "parallel": False,
            "state_bytes_per_row": SPEC.ssm_state_bytes_per_row,
            "state_dtype": "float32", "backend": "xla",
            "row_steps": status["ssm"]["row_steps"],
            "prefix_reuse": "off (recurrent state has no snapshot)"}
        assert status["ssm"]["row_steps"] >= 21 + 14 + 18 + 12 - 4
        assert status["memory"]["ssm_state_bytes"] \
            == 4 * SPEC.ssm_state_bytes_per_row
        labels = status["compiles"]["programs"]["decode_window"]["labels"]
        assert "touched" in np.atleast_1d(labels["expert_product"])
        assert "off (recurrent state has no snapshot)" in np.atleast_1d(
            labels["prefix_reuse"])
        assert "float32" in np.atleast_1d(labels["ssm_state"])
        # The CPU backend: XLA's ssm_step, and a label of the window alone.
        assert "xla" in np.atleast_1d(labels["ssm_backend"])
        assert "ssm_backend" not in status["compiles"]["programs"][
            "prefill"]["labels"]
        assert status["moe"]["experts"] == 4
        # The flight ring's column, the series on /metrics, the scope.
        await asyncio.sleep(0.05)
        cols = ring.between(t_lo, time.monotonic())["columns"]
        assert cols["ssm_row_steps"].sum() == status["ssm"]["row_steps"]
        registry = MetricsRegistry()
        perf.PerfMetricsUpdater(registry).update(engine, force=True)
        text = registry.expose().decode()
        for series in ("ssm_row_steps_total", "perf_ssm_state_info{",
                       f'bytes_per_row="{SPEC.ssm_state_bytes_per_row}"',
                       'dtype="float32"'):
            assert series in text, series
        for cache in (engine.runner._window_cache,
                      engine.runner._prefill_cache):
            fn = max(cache.values(), key=lambda w: w._calls)
            drawn = set(fn.ops_by_scope().values())
            assert any(name and "ssm" in name.split("+") for name in drawn)
            assert any(name and "mlp" in name.split("+") for name in drawn)
    finally:
        engine.stop()


@async_test
async def test_a_slot_s_next_request_answers_as_a_cold_run():
    """One slot: a request that ends mid-window, then another in the same
    slot, then the first prompt again. The second answers as a fresh engine
    answers it (the state it starts from is zero, whatever the slot held),
    and the repeat takes no prefix hit and answers as the first time."""
    cold = TPUEngine(config(max_num_seqs=1), params=PARAMS)
    cold.start()
    try:
        want, want_lp, _ = await collect(cold, prompt_of(27, 8), 12,
                                         logprobs=1)
    finally:
        cold.stop()
    engine = TPUEngine(config(max_num_seqs=1), params=PARAMS)
    engine.start()
    try:
        first, first_lp, _ = await collect(engine, prompt_of(40, 7), 6,
                                           logprobs=1)
        got, got_lp, _ = await collect(engine, prompt_of(27, 8), 12,
                                       logprobs=1)
        assert got == want
        np.testing.assert_allclose(got_lp, want_lp, atol=1e-5)
        again, again_lp, _ = await collect(engine, prompt_of(40, 7), 6,
                                           logprobs=1)
        assert again == first
        np.testing.assert_allclose(again_lp, first_lp, atol=1e-5)
        assert engine.prefix_hit_blocks == 0
        assert engine.allocator.stats()["reuse_hit_blocks"] == 0
        assert not engine.allocator.cached      # no hash was registered
    finally:
        engine.stop()


@async_test
async def test_a_preempted_row_resumes_token_for_token():
    """Three requests against a pool that cannot hold them: the youngest is
    preempted, requeued and prefilled again from its tokens with the state
    reset; every stream gets what it gets alone (``same_up_to_a_tie``: the
    re-prefill computes by chunks what the windows computed by steps)."""
    prompts = [prompt_of(24, 40 + i) for i in range(3)]
    alone = TPUEngine(config(), params=PARAMS)
    alone.start()
    try:
        want = [(await collect(alone, p, 40))[0] for p in prompts]
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
        for prompt, (toks, _, _), alone_toks in zip(prompts, results, want):
            assert len(toks) == 40
            assert same_up_to_a_tie(toks, alone_toks, prompt), (toks,
                                                                alone_toks)
    finally:
        engine.stop()


# -- the share -------------------------------------------------------------------

def test_the_four_shares_add_up_to_the_uncut_layer():
    """The routed parts of all four shares and ONE shared expert are what
    the uncut reference gives for the whole expert layer; and the program's
    layer over one share is that share's part."""
    whole = read_spec({**TOY, "n_routed_experts": 16,
                       "expert_parallel": None})
    params = seeded_params(whole, 5)
    n = 24
    x = jax.random.normal(jax.random.key(9), (n, whole.hidden_size))
    x = x.astype(jnp.bfloat16).astype(jnp.float32)
    index = 1                               # the first E of MEM*EME
    with jax.default_matmul_precision("highest"):
        total = ref.layer_of(whole)(x, params["layers"], index)
        parts = ref.layer_of(whole, parts=True)(x, params["layers"], index)
        routed = 0.0
        for first in (0, 4, 8, 12):
            spec = dataclasses.replace(whole, num_experts=4,
                                       first_expert=first)
            layers = dict(params["layers"])
            for key in ("moe_w_up", "moe_w_down"):
                layers[key] = layers[key][:, first:first + 4]
            share = ref.layer_of(spec, parts=True)(x, layers, index)
            routed = routed + share["routed"]
            if first == 4:
                mine, mine_layers, mine_spec = share, layers, spec
    np.testing.assert_allclose(routed, parts["routed"], atol=1e-5)
    np.testing.assert_allclose(x + routed + parts["shared"], total, atol=1e-5)
    assert float(jnp.abs(parts["routed"]).mean()) > 0.05
    # The program's expert layer over share 1 of 4.
    from benchmark.lib.reference import rms_norm
    lp = {k: v[0] for k, v in mine_layers.items()
          if k.startswith(("moe_", "shared_"))}
    h = rms_norm(x, mine_layers["mixer_norm"][index], whole.rms_norm_eps)
    got = model.ffn_block(h.astype(jnp.bfloat16), lp, mine_spec)
    want = mine["routed"] + mine["shared"]
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) \
        < 0.05 * float(jnp.abs(want).max())
