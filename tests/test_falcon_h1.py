"""The Falcon-H1 block (``falcon_h1``) at the configuration's rehearsal size on
the CPU, held to benchmark/references/falcon_h1.py: every layer a Mamba-2
mixer AND rotary attention side by side on ONE normed input, summed into ONE
residual under the model's muP constants, a dense SwiGLU behind them; a row
keeps a recurrent state a SLOT and K/V pages a TOKEN in every layer.

What is held: the runner's prefill and its windows (of one step and of four)
against the reference's full forward, for a padded batch of unequal prompts;
a prompt's chunk alone and over a carried state AND cached pages at lengths
around the scan's chunk; the recurrence's kernel, interpreted, at a head of
two lane tiles against ``hybrid.ssm_step``, dead and padded slots bit for
bit as they were; the window program with that kernel; a preempted row
gives back its slot and its pages and is recomputed to the reference's
logprobs; every control of the reference moves the logits; the reader makes
the catalog row's spec; each refusal names what is lacking.
"""

import asyncio
import dataclasses
import json
import os
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import async_test

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import manifest  # noqa: E402
from benchmark.lib.reference import rms_norm  # noqa: E402
from dynamo_tpu.engine import hybrid, model, recurrence  # noqa: E402
from dynamo_tpu.engine.config import (EngineConfig, FalconH1Spec,  # noqa: E402
                                      ModelSpec, UnsupportedBlockError,
                                      block_refusals)
from dynamo_tpu.engine.engine import TPUEngine  # noqa: E402
from dynamo_tpu.engine.runner import (PK_CAP, PK_LOGPROB, PK_POS,  # noqa: E402
                                      PK_PREFIX, PK_SEQLEN, PK_TOPP,
                                      ModelRunner, PrefillSeq)
from dynamo_tpu.llm.protocols import PreprocessedRequest  # noqa: E402
from dynamo_tpu.runtime.context import Context  # noqa: E402

ref = manifest.load_module("references", "falcon_h1")

PAGE = 16
FILE = manifest.load_json(os.path.join(
    manifest.BENCH, "configs", "falcon-h1-34b-pp6-int8.json"))
#: The configuration's own toy: the row's keys, the multipliers as
#: published, both branches in each of 3 layers, a scan chunk of 8 tokens.
TOY = {k: v for k, v in {**FILE, **FILE["rehearsal_model"]}.items()
       if k not in ("rehearsal_model", "launch", "stands_for", "assumed",
                    "published", "reduced", "chips", "reference", "roofline",
                    "source")}
CHUNK = TOY["mamba_chunk_size"]


def read_spec(cfg: dict) -> ModelSpec:
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "config.json")
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(cfg, fh)
        return dataclasses.replace(ModelSpec.from_hf_config(path), name="fh1")


def seeded_params(spec, seed: int):
    """init_params, then what it draws as ones drawn from the seed: A and D
    (a head's vectors end in a 1), so that heads differ in how they forget
    and in what a token gives its own output; and K's and V's projections
    at the sizes the muP constants are published FOR (scores of tenths under
    ``key_multiplier``, an attention branch that weighs what the recurrent
    one weighs), so that the logits show the rotation, what a page holds
    and how the two branches are wired."""
    params = model.init_params(spec, jax.random.key(seed))
    key = jax.random.key(seed + 100)
    layers = params["layers"]
    for i, name in enumerate(("ssm_a_log", "ssm_d")):
        layers[name] = (0.5 * jax.random.normal(
            jax.random.fold_in(key, i), layers[name].shape)).astype(
            jnp.bfloat16)
    for name, size in (("wk", 0.4 / spec.key_multiplier), ("wv", 3.0)):
        std = float(jnp.std(layers[name].astype(jnp.float32)))
        layers[name] = (layers[name].astype(jnp.float32)
                        * (size / (std * spec.hidden_size ** 0.5))
                        ).astype(jnp.bfloat16)
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


def reference_logits(tokens, params=None, spec=None, **switches):
    """The reference's logits [len(tokens), vocab], float32, every
    multiplier where it is published."""
    params, spec = params or PARAMS, spec or SPEC
    layer = ref.layer_of(spec, **switches)
    with jax.default_matmul_precision("highest"):
        x = (params["embed"][np.asarray(tokens)].astype(jnp.float32)
             * spec.scale_emb)
        for i in range(spec.num_layers):
            x = layer(x, params["layers"], jnp.int32(i))
        h = rms_norm(x, params["final_norm"], spec.rms_norm_eps)
        return np.asarray(h @ params["lm_head"].astype(jnp.float32)
                          / spec.logit_divisor)


def reference_logprobs(prompt, generated):
    """Teacher-forced logprob of each generated token."""
    tokens = list(prompt) + list(generated)
    logp = np.asarray(jax.nn.log_softmax(
        jnp.asarray(reference_logits(tokens[:-1])), axis=-1))
    return [float(logp[len(prompt) - 1 + i, t])
            for i, t in enumerate(generated)]


def near(logits, want) -> bool:
    """Served logits against the reference's: within two hundredths of
    their range (the head's 1/128 leaves logits of hundredths; bfloat16
    activations stand a few thousandths of that apart)."""
    return float(np.abs(logits - want).max()) < 0.02 * float(
        np.abs(want).max())


def close(a, b) -> bool:
    """Two lists of logprobs of the same tokens: under the head's 1/128 a
    logprob moves by thousandths of a nat between tokens, and the served
    path stands ten thousandths from the reference."""
    d = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
    return bool(np.median(d) < 3e-4 and d.max() < 1.5e-3)


async def collect(engine, prompt, max_tokens, logprobs=None):
    req = PreprocessedRequest(model="m", token_ids=list(prompt))
    req.stop_conditions.max_tokens = max_tokens
    req.stop_conditions.ignore_eos = True
    if logprobs is not None:
        req.sampling_options.logprobs = logprobs
    toks, lps = [], []
    async for out in engine.generate(req, Context()):
        toks.extend(out.get("token_ids", []))
        lps.extend(out.get("log_probs") or [])
        if out.get("finish_reason"):
            break
    return toks, lps


# -- the reader ----------------------------------------------------------------

def catalog_row() -> dict:
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path, encoding="utf-8") as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    return next(r for r in rows if r["name"] == "Falcon-H1-34B-Instruct")


def test_the_reader_makes_the_catalog_row_s_spec():
    spec = read_spec(catalog_row()["config"])
    assert isinstance(spec, FalconH1Spec)
    assert (spec.num_layers, spec.ssm_layers, spec.pool_layers,
            spec.expert_layers) == (72, 72, 72, 0)
    assert spec.parallel_mixers and spec.attn_rope
    assert spec.layer_pattern == "M*D" * 72 and spec.ssm_kind == "M"
    groups = hybrid.groups_of(spec)
    assert groups.mixer_index == groups.attn_index == tuple(range(72))
    assert spec.kv_entry == (4, (128, 128))
    assert spec.kv_bytes_per_token() == 72 * 4 * 128 * 2 * 2
    assert spec.ssm_state_shapes == ((32, 128, 256), (3, 5120))
    assert spec.ssm_state_bytes_per_row == 72 * (32 * 128 * 256 * 4
                                                 + 3 * 5120 * 2)
    assert (spec.scale_emb, spec.logit_divisor) == (5.656854249492381, 128.0)
    assert spec.key_multiplier == 0.011048543456039804
    assert (spec.attn_in_multiplier, spec.attn_out_multiplier,
            spec.ssm_in_multiplier, spec.ssm_out_multiplier) == (
        1.0, 0.0375, 0.25, 0.08838834764831845)
    assert spec.ssm_multipliers == (0.3535533905932738, 0.25,
                                    0.1767766952966369, 0.5,
                                    0.3535533905932738)
    assert spec.mlp_multipliers == (0.1767766952966369,
                                    0.011160714285714284)
    assert spec.rope_theta == 1e11
    shapes = model.param_shapes(spec)["layers"]
    assert shapes["mixer_norm"] == (144, 5120)      # two a layer, not three
    assert shapes["ssm_w_in"] == (72, 5120, 4096 + 5120)       # z | xBC
    assert shapes["ssm_w_dt"] == (72, 5120, 32)
    assert shapes["ssm_conv_w"] == (72, 4, 5120)
    assert shapes["wq"] == (72, 5120, 2560)
    assert shapes["wk"] == shapes["wv"] == (72, 5120, 512)
    assert "wz" not in shapes
    # Every matrix of a layer is an int8 leaf [in, out], as the
    # configuration states and as a checkpoint holds them.
    from dynamo_tpu.engine.quant import QUANT_LAYER_KEYS
    assert {k for k in shapes if k.startswith("w") or "_w_" in k} - {
        "ssm_conv_w"} <= set(QUANT_LAYER_KEYS)
    assert shapes["w_gate"] == (72, 5120, 21504)
    assert spec.num_params() == 33_642_516_224
    # The chip's stage: 12 of the 72 layers, both tables.
    stage = read_spec({**catalog_row()["config"], "num_hidden_layers": 12})
    assert stage.num_params() == 7_835_314_304
    assert stage.ssm_state_bytes_per_row == 50_700_288
    assert stage.kv_bytes_per_token() == 24_576
    int8 = dataclasses.replace(stage, quant="int8")
    assert 7.8 < int8.weight_read_step_ms(819.0) * 0.819 < 7.9     # GB
    # The configuration's file is the row's keys but for the depth.
    row = catalog_row()["config"]
    assert {k: FILE[k] for k in row} == {**row, "num_hidden_layers": 12}


@pytest.mark.parametrize("key,value,names", [
    ("mamba_norm_before_gate", True, "BEFORE the grouped norm"),
    ("attn_layer_indices", [0, 2], "EVERY layer attends"),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}, "plain frequencies"),
    ("mamba_conv_bias", False, "bias"),
    ("attention_bias", True, "bias leaf"),
    ("ssm_multipliers", [0.5, 0.5, 0.5, 0.5], "five segments"),
    ("mamba_d_ssm", 48, "mamba_n_heads x mamba_d_head"),
])
def test_the_reader_refuses_what_is_not_written_down(key, value, names):
    with pytest.raises(UnsupportedBlockError, match=names):
        read_spec({**TOY, key: value})


def test_an_unknown_model_type_is_no_longer_this_block_s_dense_twin():
    """Before the reader knew ``falcon_h1`` the row read as a dense block
    of its attention and feed-forward widths."""
    dense = read_spec({**TOY, "model_type": "llama"})
    assert not dense.recurrent and not dense.parallel_mixers
    assert SPEC.recurrent and SPEC.pool_layers == SPEC.ssm_layers == 3


def test_a_pattern_that_is_not_pairs_side_by_side_is_refused():
    fields = {f.name: getattr(SPEC, f.name)
              for f in dataclasses.fields(SPEC)}
    with pytest.raises(ValueError, match="side by side"):
        FalconH1Spec(**{**fields, "layer_pattern": "MD*D" + "M*D" * 2})
    # The letters' groups are the scan's as before: a sequential pattern
    # still has three norms a group of three.
    assert model.param_shapes(SPEC)["layers"]["mixer_norm"] == (6, 64)


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


def test_the_other_blocks_keep_their_fields():
    dense = ModelSpec()
    assert not dense.parallel_mixers and not dense.attn_rope
    assert dense.ssm_multipliers is None and dense.mlp_multipliers is None
    assert (dense.key_multiplier, dense.attn_out_multiplier,
            dense.ssm_out_multiplier) == (1.0, 1.0, 1.0)
    assert block_refusals(dense, EngineConfig(model=dense)) == []


# -- the runner ------------------------------------------------------------------

def _window(runner, rows: dict, steps: int):
    """One window over ``rows`` {slot: (position, pages)}; returns the
    tokens and logprobs [steps, slots] and what the window counted."""
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


@pytest.mark.parametrize("steps", [1, 4], ids=["single step", "window of 4"])
def test_a_padded_batch_of_unequal_prompts_and_its_windows(steps):
    """Three prompts of 9, 21 and 30 tokens in one bucket of 32, then eight
    decode steps over their slots (as windows of one step and of four) with
    a dead slot between them: each row's logits and logprobs are the
    reference's for its own tokens, through its own state AND its own
    pages, rotated at its own positions; the dead slot's state stays as it
    was, and the window counts its live rows."""
    runner = ModelRunner(config(decode_window=steps), params=PARAMS)
    prompts = [prompt_of(n, 30 + n) for n in (9, 21, 30)]
    slots, pages = [0, 1, 3], [[1, 2], [3, 4, 5], [6, 7, 8]]
    seqs = [PrefillSeq(tokens=np.asarray(p, np.int32), start_pos=0,
                       chunk_pages=np.asarray(pg[:2], np.int32),
                       hist_pages=None, sampling=(0.0, 0, 1.0))
            for p, pg in zip(prompts, pages)]
    runner.ssm_state = runner.ssm_state.at[:, 2].set(7.0)
    runner.conv_state = runner.conv_state.at[:, :, 2].set(3.0)
    first = np.asarray(runner.prefill_batch(seqs, slots=slots)["tokens"])
    logits = np.asarray(runner.last_prefill_logits, np.float32)
    for row, prompt in enumerate(prompts):
        assert near(logits[row], reference_logits(prompt)[-1])
    rows = {s: (len(p), pg) for s, p, pg in zip(slots, prompts, pages)}
    toks, lps = [], []
    for w in range(8 // steps):
        t, lp, counted = _window(
            runner, {s: (pos + steps * w, pg)
                     for s, (pos, pg) in rows.items()}, steps)
        toks.append(t)
        lps.append(lp)
        assert float(np.asarray(counted["ssm"])[0]) == 3.0 * steps
    toks, lps = np.concatenate(toks), np.concatenate(lps)
    for row, (slot, prompt) in enumerate(zip(slots, prompts)):
        generated = [int(t) for t in toks[:, slot]]
        want = reference_logprobs(prompt + [int(first[row])], generated)
        assert close(lps[:, slot], want), (slot, lps[:, slot], want)
    assert float(jnp.abs(runner.ssm_state[:, 2] - 7.0).max()) == 0.0
    assert float(jnp.abs(runner.conv_state[:, :, 2].astype(jnp.float32)
                         - 3.0).max()) == 0.0
    assert runner.ssm_state.shape == (3, 4, 4, 8, 16)
    assert runner.k_cache.shape[0] == 3     # a pool layer a layer
    memory = runner.memory_breakdown()
    assert memory["ssm_state_bytes"] == 4 * SPEC.ssm_state_bytes_per_row \
        == runner.ssm_state.nbytes + runner.conv_state.nbytes


@pytest.mark.parametrize("carried", [False, True],
                         ids=["alone", "over a state and pages"])
@pytest.mark.parametrize("n", [1, CHUNK - 1, CHUNK, CHUNK + 1,
                               3 * CHUNK + 5])
def test_a_chunk_of_a_prompt_is_the_recurrence_a_token_at_a_time(n, carried):
    """A prompt's chunk of n tokens (around the scan's chunk of 8) through
    the prefill program, alone from position 0 or behind a first chunk of
    16 tokens whose state lies in the row's slot and whose K and V lie in
    its page: the last token's logits are the reference's, which computes
    the recurrence a token at a time and attends every key at once."""
    runner = ModelRunner(config(), params=PARAMS)
    head = prompt_of(PAGE, 5) if carried else []
    tail = prompt_of(n, 60 + n)
    pages = np.asarray([4, 5, 6, 7], np.int32)
    if carried:
        runner.prefill_batch([PrefillSeq(
            tokens=np.asarray(head, np.int32), start_pos=0,
            chunk_pages=pages[:1], hist_pages=None,
            sampling=(0.0, 0, 1.0))], slots=[2])
    runner.prefill_batch([PrefillSeq(
        tokens=np.asarray(tail, np.int32), start_pos=len(head),
        chunk_pages=pages[len(head) // PAGE:][:-(-n // PAGE)],
        hist_pages=pages[:1] if carried else None,
        sampling=(0.0, 0, 1.0))], slots=[2])
    logits = np.asarray(runner.last_prefill_logits, np.float32)[0]
    assert near(logits, reference_logits(head + tail)[-1])


# -- the kernel at a head of two lane tiles ---------------------------------------

#: The toy's widths with a state of 256 lanes: two lane tiles a row of S.
WIDE = read_spec({**TOY, "mamba_d_state": 256})


def _bits(a) -> np.ndarray:
    return np.asarray(a).view(np.int32)


@pytest.mark.parametrize("live", [[True, False, True, True, False, False],
                                  [False] * 6, [True] * 6],
                         ids=["holes", "no row live", "every row live"])
def test_the_kernel_at_a_state_of_256_lanes_is_the_step_s_definition(live):
    """engine/recurrence.py through the Pallas interpreter at a head [8, 256]
    (two lane tiles a row of S, 4 heads in 2 groups, ONE head a copy at the
    published size; here the copy holds all four), three steps of the middle
    layer of a stack of three: a live row's output and new state are
    ``hybrid.ssm_step``'s to float32 rounding; a dead slot (NaN in its
    state: whoever reads or writes it shows) and the other layers keep their
    state BITWISE."""
    rows, layer = len(live), 1
    params = seeded_params(WIDE, 3)
    lp = {k: v[layer] for k, v in params["layers"].items()
          if k.startswith("ssm_")}
    h = jax.random.normal(jax.random.key(9), (rows, 3, WIDE.hidden_size)
                          ).astype(jnp.bfloat16)
    s_shape, c_shape = WIDE.ssm_state_shapes
    assert s_shape == (4, 8, 256)
    on = np.asarray(live)
    states = 0.5 * jax.random.normal(jax.random.key(8),
                                     (3, rows, *s_shape), jnp.float32)
    states = jnp.where(jnp.asarray(on)[None, :, None, None, None], states,
                       jnp.nan)
    conv = jnp.zeros((c_shape[0], rows, c_shape[1]), jnp.bfloat16)
    want, conv_want = states[layer], conv
    step = jax.jit(lambda *a: hybrid.ssm_step_live(
        *a[:2], WIDE, *a[2:], interpret=True))
    define = jax.jit(lambda *a: hybrid.ssm_step(*a[:2], WIDE, *a[2:]))
    alive = jnp.asarray(on)
    for t in range(3):
        held = states
        out_want, want, conv_want = define(h[:, t], lp, want, conv_want,
                                           alive)
        out, states, conv = step(h[:, t], lp, states, jnp.int32(layer),
                                 conv, alive, hybrid.live_walk(alive))
        if on.any():
            np.testing.assert_allclose(
                np.asarray(out, np.float32)[on],
                np.asarray(out_want, np.float32)[on],
                atol=0.01 * float(jnp.abs(
                    out_want.astype(jnp.float32)[on]).max()))
            np.testing.assert_allclose(np.asarray(states[layer])[on],
                                       np.asarray(want)[on], rtol=2e-6,
                                       atol=1e-6)
        np.testing.assert_array_equal(_bits(states[layer])[~on],
                                      _bits(held[layer])[~on])
        np.testing.assert_array_equal(_bits(states[::2]), _bits(held[::2]))
    assert recurrence.chunk_heads(32, 4 * 128 * 256) == 1   # the cell's


def test_the_window_program_with_the_kernel_is_the_window_program_without():
    """Two windows of four steps behind a prompt, once with XLA's update of
    every slot and once with the kernel (interpreted) over the live slots,
    both branches live in every layer: the same logprobs to float32
    rounding, the reference's; the state of a slot nobody serves stays
    bitwise under the kernel."""
    prompt = prompt_of(21, 77)
    seq = PrefillSeq(tokens=np.asarray(prompt, np.int32), start_pos=0,
                     chunk_pages=np.asarray([1, 2], np.int32),
                     hist_pages=None, sampling=(0.0, 0, 1.0))
    got = {}
    for backend in ("xla", "kernel"):
        runner = ModelRunner(config(), params=PARAMS)
        if backend == "kernel":
            runner.backends = dataclasses.replace(
                runner.backends, ssm="kernel", interpret=True)
        runner.ssm_state = runner.ssm_state.at[:, 3].set(2.5)
        first = int(np.asarray(
            runner.prefill_batch([seq], slots=[1])["tokens"])[0])
        toks, lps = [], []
        for w in range(2):
            t, lp, _ = _window(runner, {1: (21 + 4 * w, [1, 2, 3])}, 4)
            toks += [int(x) for x in t[:, 1]]
            lps += [float(x) for x in lp[:, 1]]
        got[backend] = (first, toks, lps)
        assert float(jnp.abs(runner.ssm_state[:, 3] - 2.5).max()) == 0.0
    assert got["xla"][:2] == got["kernel"][:2]
    np.testing.assert_allclose(got["kernel"][2], got["xla"][2], atol=2e-5)
    first, toks, lps = got["kernel"]
    assert close(lps, reference_logprobs(prompt + [first], toks))


# -- the reference's controls ----------------------------------------------------

CONTROLS = ["parallel=false", "ssm=false", "attn=false", "rope=false",
            "key_multiplier=1", "branch_multipliers=1", "ssm_multipliers=1",
            "mlp_multipliers=1", "gate_before_norm=false", "conv=false",
            "skip=false", "recurrence=false", "mlp=false", "state=bfloat16",
            "precision=bfloat16", "precision=float8_e4m3fn"]


@pytest.mark.parametrize("control", CONTROLS)
def test_every_control_of_the_reference_changes_the_logits(control):
    """Each switch of ``make_layers`` computes another function: the logits
    of a 40-token prompt move (a bfloat16 state least of all: by float32's
    own rounding times a few)."""
    key, _, value = control.partition("=")
    tokens = prompt_of(40, 3)
    want = reference_logits(tokens)
    got = reference_logits(tokens, **{key: value})
    moved = float(np.abs(got - want).max()) / float(np.abs(want).max())
    assert moved > (1e-6 if key == "state" else 1e-4), moved


def test_the_served_path_is_nearer_the_reference_than_to_a_sequential_group():
    """What the program had before this block (attention behind the mixer's
    sum, each behind its own norm) is the control ``parallel=false``: the
    served logits stand a fifth as far from the reference as from it."""
    runner = ModelRunner(config(), params=PARAMS)
    prompt = prompt_of(30, 12)
    runner.prefill_batch([PrefillSeq(
        tokens=np.asarray(prompt, np.int32), start_pos=0,
        chunk_pages=np.asarray([1, 2], np.int32), hist_pages=None,
        sampling=(0.0, 0, 1.0))], slots=[0])
    logits = np.asarray(runner.last_prefill_logits, np.float32)[0]
    right = np.abs(logits - reference_logits(prompt)[-1]).max()
    wrong = np.abs(logits - reference_logits(prompt, parallel=False)[-1]
                   ).max()
    assert right < 0.2 * wrong, (right, wrong)


# -- the engine ------------------------------------------------------------------

@async_test
async def test_the_engine_serves_it_and_says_what_a_row_keeps():
    """Whole-prompt prefill then decode windows; a prompt past the chunk
    budget in three chunks (the state carried across chunk borders, the
    attention over history pages); the start-up facts."""
    engine = TPUEngine(config(max_prefill_tokens=32), params=PARAMS)
    engine.start()
    try:
        for seed, n, cap in ((1, 19, 13), (3, 80, 10)):
            prompt = prompt_of(n, seed)
            got, lps = await collect(engine, prompt, cap, logprobs=1)
            assert len(got) == cap
            assert close(lps, reference_logprobs(prompt, got)), (n, lps)
        assert engine.chunk_dispatch_count >= 3
        assert engine.prefix_hit_blocks == 0
        status = engine.perf_status()
        assert status["ssm"] == {
            "layers": 3, "kind": "mamba2", "parallel": True,
            "state_bytes_per_row": SPEC.ssm_state_bytes_per_row,
            "state_dtype": "float32", "backend": "xla",
            "row_steps": status["ssm"]["row_steps"],
            "prefix_reuse": "off (recurrent state has no snapshot)"}
        assert status["ssm"]["row_steps"] >= 13 + 10 - 2
        fn = max(engine.runner._window_cache.values(),
                 key=lambda w: w._calls)
        drawn = {part for name in fn.ops_by_scope().values() if name
                 for part in name.split("+")}
        assert {"ssm", "ssm.state", "ssm.conv", "attn.qkv", "attn.core",
                "attn.out", "mlp"} <= drawn, drawn
    finally:
        engine.stop()


@async_test
async def test_a_preempted_row_gives_back_its_slot_and_pages_and_resumes():
    """Three requests against a pool that cannot hold them: the youngest is
    preempted (its slot and its pages go back together), requeued and
    prefilled again from its tokens with the state reset; every stream's
    logprobs are the reference's for the tokens it got."""
    prompts = [prompt_of(24, 40 + i) for i in range(3)]
    engine = TPUEngine(config(num_pages=9), params=PARAMS)
    engine.start()
    try:
        tasks = []
        for prompt in prompts:
            tasks.append(asyncio.ensure_future(
                collect(engine, prompt, 40, logprobs=1)))
            await asyncio.sleep(0.05)
        results = await asyncio.gather(*tasks)
        assert engine.preempt_count > 0
        for prompt, (toks, lps) in zip(prompts, results):
            assert len(toks) == 40
            assert close(lps, reference_logprobs(prompt, toks)), lps
        await asyncio.sleep(0.1)
        stats = engine.allocator.stats()
        assert stats["pages_active"] == 0 and stats["pages_free"] == 8
    finally:
        engine.stop()
