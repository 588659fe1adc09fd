"""The GLM-4.7-Flash block (``glm4_moe_lite``) at a toy size on the CPU, held
to benchmark/references/glm4_moe_lite.py: latent attention WITHOUT an indexer
(a pool of ONE array), a share of the routed experts, and the model's own
prediction module as the draft of every step of the window program
(``spec_decode="mtp"``).

What is held: served logprobs under drafting against the reference's full
forward; the module's drafts against the reference's ``draft_logits``; greedy
output token-identical to the plain window's at a vocabulary small enough that
drafts are accepted AND rejected; logprobs under drafting equal the plain
window's; a row at its cap emits one token; a prompt over a cached prefix
leaves the module's entries and drafts as cold; the four shares add up to the
uncut layer; each new refusal names what is lacking.
"""

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
from dynamo_tpu.engine import model  # noqa: E402
from dynamo_tpu.engine.config import (DeepseekV32Spec, EngineConfig,  # noqa: E402
                                      ModelSpec, PRESETS,
                                      UnsupportedBlockError, block_refusals)
from dynamo_tpu.engine.engine import TPUEngine  # noqa: E402
from dynamo_tpu.engine.runner import (PK_CAP, PK_LOGPROB, PK_POS,  # noqa: E402
                                      PK_PREFIX, PK_SEQLEN, ModelRunner,
                                      PrefillSeq)
from dynamo_tpu.llm.protocols import PreprocessedRequest  # noqa: E402
from dynamo_tpu.runtime.context import Context  # noqa: E402

ref = manifest.load_module("references", "glm4_moe_lite")

PAGE = 16
#: The catalog row's keys at a toy size: a dense layer, two expert layers
#: that hold experts 4 to 7 of 16, the module, a vocabulary of 12.
TOY = {
    "model_type": "glm4_moe_lite", "attention_bias": False,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 96,
    "max_position_embeddings": 2048, "moe_intermediate_size": 32,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 4, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 4, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 2,
    "first_k_dense_replace": 1, "num_hidden_layers": 3,
    "num_key_value_heads": 4, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 48,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "vocab_size": 12,
    "expert_parallel": {"routed_experts": 16, "first_expert": 4},
}


def read_spec(cfg: dict) -> ModelSpec:
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "config.json")
        with open(path, "w", encoding="utf-8") as fh:
            json.dump({k: v for k, v in cfg.items()
                       if not (k == "expert_parallel" and v is None)}, fh)
        return dataclasses.replace(ModelSpec.from_hf_config(path), name="glm")


SPEC = read_spec(TOY)
PARAMS = model.init_params(SPEC, jax.random.key(11))
# A decisive router: with logits of unit size the choice of 2 of 16 flips
# between two roundings of one state every few tokens, and a flip moves a
# logprob by tenths of a nat on either path.
for _name in ("moe_gate", "mtp_moe_gate"):
    PARAMS["layers"][_name] = PARAMS["layers"][_name] * 8.0


def config(**kw) -> EngineConfig:
    defaults = dict(model=SPEC, page_size=PAGE, num_pages=128,
                    max_pages_per_seq=16, max_num_seqs=4,
                    prefill_buckets=(32, 64, 128), max_prefill_tokens=64,
                    attention_backend="xla", decode_window=4,
                    pipeline_depth=2)
    defaults.update(kw)
    return EngineConfig(**defaults)


def drafting(**kw) -> EngineConfig:
    return config(spec_decode="mtp", spec_k=1, **kw)


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


def close(a, b, n=None) -> bool:
    """Two lists of logprobs of the same tokens agree: the median within
    0.02 nat and nine in ten within 0.1. (The toy's router chooses 2 of 16
    and holds 4: a choice that flips between two roundings of the same
    state moves a token by tenths of a nat, in the plain window against
    the reference as much as in the drafting one.)"""
    d = np.abs(np.asarray(a[:n], np.float64) - np.asarray(b[:n], np.float64))
    return bool(np.median(d) < 0.02 and (d > 0.1).mean() <= 0.1)


def same_up_to_a_tie(prompt, want, got) -> int:
    """Tokens equal; or, at the first that differs, the reference gives
    the two candidates logprobs within 0.3 nat (a near-tie that two
    roundings break differently), past which the contexts differ and
    nothing more is compared. Returns how many tokens were equal."""
    for i, (a, b) in enumerate(zip(want, got)):
        if a != b:
            lp = [ref.reference_logprobs(PARAMS, SPEC, prompt,
                                         list(want[:i]) + [t])[-1]
                  for t in (a, b)]
            assert abs(lp[0] - lp[1]) < 0.3, (i, a, b, lp)
            return i
    return min(len(want), len(got))


# -- the reader and the spec --------------------------------------------------

def test_the_reader_takes_the_catalog_row_s_keys():
    """``glm4_moe_lite`` reads onto the latent spec with NO indexer (a pool
    of one array), one prediction module, plain rope and the share."""
    assert isinstance(SPEC, DeepseekV32Spec)
    assert (SPEC.index_topk, SPEC.index_n_heads, SPEC.index_head_dim) == (
        0, 0, 0)
    assert SPEC.kv_entry == (1, (128, 0))
    assert (SPEC.mtp_layers, SPEC.pool_layers) == (1, 4)
    assert SPEC.rope_yarn is None and SPEC.attn_scale == 24 ** -0.5
    assert (SPEC.router_width, SPEC.first_expert, SPEC.num_experts) == (
        16, 4, 4)
    shapes = model.param_shapes(SPEC)
    assert not any("index_" in k for k in shapes["layers"])
    assert shapes["layers"]["mtp_w_eh"] == (1, 128, 64)
    assert shapes["layers"]["mtp_moe_w_gate"] == (1, 4, 64, 32)
    leaves = jax.tree.leaves(shapes, is_leaf=lambda x: isinstance(x, tuple))
    assert SPEC.num_params() == sum(int(np.prod(s)) for s in leaves)


def test_the_published_widths_count_what_the_issue_counts():
    """At the published widths the chip's share holds the parameters the
    roofline module counts from the same keys: 9.30 G values, 61,440 B of
    entries a token."""
    cfg = manifest.load_json(os.path.join(
        manifest.BENCH, "configs", "glm-4.7-flash-ep4-int8.json"))
    spec = read_spec(cfg)
    assert spec.num_params() == 9_296_812_992
    assert EngineConfig(model=spec).kv_token_bytes() == 48 * 640 * 2
    # The issue's count of the matrices alone (9,296,543,744) lacks the
    # norms, routers and selection biases: 269,248 values.
    assert 0 < spec.num_params() - 9_296_543_744 < 300_000


@pytest.mark.parametrize("change, lacks", [
    ({"num_nextn_predict_layers": 2}, "ONE prediction module"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "scaled rotation"),
    ({"index_topk": 2048}, "indexer"),
    ({"n_shared_experts": 2}, "n_shared_experts"),
    ({"partial_rotary_factor": 0.5}, "partial_rotary_factor"),
])
def test_the_reader_refuses_what_is_not_written_down(change, lacks):
    with pytest.raises(UnsupportedBlockError, match=lacks):
        read_spec({**TOY, **change})


@pytest.mark.parametrize("spec, kw, lacks", [
    (PRESETS["tiny-test"], dict(spec_decode="mtp", spec_k=1),
     "no such module"),
    (SPEC, dict(spec_decode="mtp", spec_k=2), "spec_k 2"),
    (SPEC, dict(spec_decode="ngram"), "n-gram drafting"),
    (dataclasses.replace(SPEC, mtp_layers=0), dict(spec_decode="ngram"),
     "absorbed latent product"),
    (dataclasses.replace(SPEC, index_topk=64, index_n_heads=4,
                         index_head_dim=16),
     dict(spec_decode="mtp", spec_k=1), "no indexer's selection"),
    (SPEC, dict(spec_decode="mtp", spec_k=1, tp=2), "ONE device"),
])
def test_each_new_refusal_names_what_is_lacking(spec, kw, lacks):
    cfg = EngineConfig(model=spec, page_size=PAGE, num_pages=32, **kw)
    found = [str(r) for r in block_refusals(spec, cfg)]
    assert any(lacks in text for text in found), found


# -- the window program against the reference -----------------------------------

def serve(runner, prompt, windows, window=4, cap=None, pages=None):
    """Prefill ``prompt`` into slot 0 and run ``windows`` windows; returns
    (tokens, logprobs, drafts verified [(index, token)], emitted a step)."""
    pages = np.arange(1, 9, dtype=np.int32) if pages is None else pages
    n = len(prompt)
    mtp = runner.config.spec_decode == "mtp"
    seq = PrefillSeq(tokens=np.asarray(prompt, np.int32), start_pos=0,
                     chunk_pages=pages[:-(-n // PAGE)], hist_pages=None,
                     sampling=(0.0, 0, 1.0), next_page=int(pages[n // PAGE]))
    first = int(np.asarray(runner.prefill_batch([seq], slots=[0])["tokens"])[0])
    toks, lps, drafts, emitted = [first], [], [], []
    pos = n
    for _ in range(windows):
        packed = np.zeros((4, PK_PREFIX + 8), np.int32)
        packed[0, PK_POS], packed[0, PK_SEQLEN] = pos, pos + 1
        packed[0, PK_CAP] = len(pages) * PAGE if cap is None else cap
        packed[0, PK_LOGPROB] = 1
        packed[0, PK_PREFIX:PK_PREFIX + len(pages)] = pages
        out = runner.decode_window(packed, window)
        t, lp = np.asarray(out[0]), np.asarray(out[1])
        if not mtp:
            toks += t[:, 0].tolist()
            lps += lp[:, 0].tolist()
            pos += window
            continue
        emit, draft = (np.asarray(out[4][k]) for k in ("emit", "draft"))
        for m in range(window):
            e = int(emit[m, 0])
            emitted.append(e)
            if draft[m, 0] >= 0:
                drafts.append((n + len(toks), int(draft[m, 0])))
            toks += t[m, 0, :e].tolist()
            lps += lp[m, 0, :e].tolist()
            pos += e
    return toks, lps, drafts, emitted


@pytest.fixture(scope="module")
def served():
    prompt = prompt_of(21, 5)
    plain = serve(ModelRunner(config(), params=PARAMS), prompt, 8)
    draft = serve(ModelRunner(drafting(), params=PARAMS), prompt, 6)
    return prompt, plain, draft


def test_drafting_windows_agree_with_the_reference_s_forward(served):
    """Prefill, then drafting windows: the logprob served for every emitted
    token against the reference's full forward, teacher-forced."""
    prompt, _, (toks, lps, _, emitted) = served
    assert set(emitted) <= {1, 2} and len(toks) == 1 + sum(emitted)
    full = ref.reference_logprobs(PARAMS, SPEC, prompt, toks)
    assert close(lps, full[1:])


def test_the_module_s_drafts_are_the_reference_s(served):
    """Every draft the window verified (the first made by prefill, the rest
    by the module inside the window) against ``draft_logits``: the argmax
    of row p - 2, or within a tenth of the row's deviation of it."""
    prompt, _, (toks, _, drafts, _) = served
    assert len(drafts) >= 20
    rows = np.asarray(ref.draft_logits(PARAMS, SPEC, list(prompt) + toks))
    gaps = np.asarray([(rows[p - 2].max() - rows[p - 2][t])
                       / rows[p - 2].std() for p, t in drafts])
    # The argmax, but for a few whose expert choice flipped between the
    # two roundings (tenths of a deviation off, not whole ones).
    assert np.mean(gaps == 0.0) >= 0.85 and np.mean(gaps > 0.1) <= 0.1
    assert gaps.max() < 1.5
    # What a module reading the wrong position would draft is far off.
    off = [rows[p - 1].max() - rows[p - 1][t] for p, t in drafts
           if p - 1 < len(rows)]
    assert np.mean(off) > 0.25 * rows.std()


def test_greedy_tokens_and_logprobs_are_the_plain_window_s(served):
    _, (plain_toks, plain_lps, _, _), (toks, lps, _, _) = served
    prompt = served[0]
    n = same_up_to_a_tie(prompt, plain_toks, toks)
    assert n >= 25
    assert close(lps, plain_lps, n - 1)


def test_a_row_at_its_cap_emits_one_token():
    """At cap - 1 the draft's own position is past the row's pages: the
    step verifies the chained token alone, emits one token and the row
    freezes."""
    prompt = prompt_of(21, 5)
    runner = ModelRunner(drafting(), params=PARAMS)
    _, _, drafts, emitted = serve(runner, prompt, 1, cap=len(prompt) + 1)
    assert emitted == [1, 0, 0, 0] and drafts == []
    _, _, drafts, emitted = serve(ModelRunner(drafting(), params=PARAMS),
                                  prompt, 1, cap=len(prompt) + 2)
    assert emitted[0] >= 1 and sum(emitted) == 2 and len(drafts) <= 2


def module_entries(runner, pages, n):
    """The module's layer of the pool, slots 1 to n of a row's pages."""
    layer = np.asarray(runner.k_cache[SPEC.num_layers, 0], np.float32)
    flat = layer[np.asarray(pages)].reshape(-1, layer.shape[-1])
    return flat[1:n + 1]


def test_a_cached_prefix_leaves_the_module_as_cold():
    """A page's hash covers the tokens up to its end, and the module's
    entry of position i needs t_{i+1}: kept at slot i + 1, a shared page
    holds only what its own tokens decide. P over the two pages another
    prompt Q left (same 32 tokens, another 33rd) has the module's entries
    and drafts it has cold."""
    head = prompt_of(32, 7)
    p, q = head + [3, 1, 4, 1, 5, 9, 2, 6], head + [8, 2, 7, 1, 8, 2, 8, 1]
    cold = ModelRunner(drafting(), params=PARAMS)
    cold_toks, _, cold_drafts, _ = serve(cold, p, 3)
    want = module_entries(cold, [1, 2, 3], len(p))

    warm = ModelRunner(drafting(), params=PARAMS)
    serve(warm, q, 1)                            # pages 1, 2, 3 hold Q
    pages = np.asarray([1, 2, 4, 5], np.int32)   # P: Q's two full pages
    seq = PrefillSeq(tokens=np.asarray(p[32:], np.int32), start_pos=32,
                     chunk_pages=pages[2:3], hist_pages=pages[:2],
                     sampling=(0.0, 0, 1.0), next_page=int(pages[2]))
    first = int(np.asarray(warm.prefill_batch([seq], slots=[0])["tokens"])[0])
    got = module_entries(warm, [1, 2, 4], len(p))
    assert first == cold_toks[0]
    np.testing.assert_allclose(got, want, atol=0.06)
    # Slot 32 is where the two prompts' modules differ: P's own page.
    assert np.abs(module_entries(warm, [1, 2, 3], len(p))[31]
                  - want[31]).max() > 0.2
    assert int(np.asarray(warm.draft_dev)[0]) == cold_drafts[0][1]


# -- the step's write into the window's buffer ----------------------------------

def bits(a) -> np.ndarray:
    """A bfloat16 array's bits: equality that knows no tolerance."""
    return np.asarray(a).view(np.uint16)


def parent_s_write(buf, new, start, interpret=False):
    """What a step's write was before it was a kernel's."""
    return jax.lax.dynamic_update_slice(buf, new, (0, 0, start, 0))


@pytest.mark.parametrize("shape, s", [
    ((4, 3, 16, 128), 2),       # the window's columns: two tiles of 8 rows
    ((1, 4, 16, 256), 2),       # the hidden states beside them, one "layer"
    ((6, 2, 4, 128), 2),        # a window shorter than a tile moves whole
    ((2, 2, 12, 128), 3),       # and so does one whose rows cross tiles
    ((5, 2, 8, 128), 1),        # layers that no block size divides: 5 x 1
])
def test_the_step_s_write_is_dynamic_update_slice_at_every_step(shape, s):
    """attention.write_window_rows_pallas, interpreted, against
    ``dynamic_update_slice`` at every step of a window, bit for bit, the
    buffer carried from step to step as the scan carries it."""
    from dynamo_tpu.engine.attention import write_window_rows_pallas
    buf = want = jax.random.normal(jax.random.key(1), shape, jnp.bfloat16)
    for m in range(shape[2] // s):
        new = jax.random.normal(jax.random.key(2 + m),
                                (*shape[:2], s, shape[3]), jnp.bfloat16)
        buf = write_window_rows_pallas(buf, new, jnp.int32(m * s),
                                       interpret=True)
        want = parent_s_write(want, new, m * s)
        assert (bits(buf) == bits(want)).all(), m


def _decided(head_of) -> dict:
    """PARAMS with a head that decides every draft's fate: ``head_of``
    gives the head's columns for tokens 1 and 2 from a fixed vector."""
    v = jax.random.normal(jax.random.key(3), PARAMS["lm_head"].shape[:1],
                          jnp.bfloat16)
    head = jnp.zeros_like(PARAMS["lm_head"])
    head = head.at[:, 1].set(head_of(v)).at[:, 2].set(-head_of(v))
    return {**PARAMS, "lm_head": head}


#: Every logit 0: the model draws token 0 and the module drafts it.
ACCEPT = _decided(lambda v: 0 * v)
#: The model's logits are (0, a, -a, 0, ...): it draws 1 or 2; the module's
#: head norm is 0, so its logits are 0 and its draft is token 0.
REJECT = _decided(lambda v: v)
REJECT["layers"] = {**PARAMS["layers"], "mtp_head_norm": jnp.zeros_like(
    PARAMS["layers"]["mtp_head_norm"])}
_ROW = (prompt_of(21, 5), np.arange(1, 9, dtype=np.int32), None)
#: name -> (params, {slot: (prompt, pages, cap)}, what the steps' emits of
#: the three windows [12, B] must look like for the scene to be the scene).
SCENES = {
    "every draft rejected": (REJECT, {0: _ROW},
                             lambda e: (e[:, 0] == 1).all()),
    "every draft accepted": (ACCEPT, {0: _ROW},
                             lambda e: (e[:, 0] == 2).all()),
    # Its third step's pair lies at positions 31 and 32.
    "a page's border inside a step of the first window": (
        ACCEPT, {0: (prompt_of(27, 6), _ROW[1], None)},
        lambda e: (e[:, 0] == 2).all()),
    "a row at its cap inside the first window": (
        PARAMS, {0: (_ROW[0], _ROW[1], 24)},
        lambda e: e[:4, 0].sum() == 3 and e[4:].sum() == 0),
    "a dead slot between two live ones": (
        PARAMS, {0: _ROW, 2: (prompt_of(19, 9),
                              np.arange(9, 17, dtype=np.int32), None)},
        lambda e: (e[:, 1] == 0).all() and (e[:, [0, 2]] > 0).all()),
}


def drafted(scene: str, windows: int = 3, window: int = 4) -> list:
    """The scene's rows prefilled into their slots, then ``windows``
    drafting windows over them beside the Pallas reader and the in-place
    commit, interpreted. After prefill and after each window: (tokens
    [M, B, S], emit [M, B], the pool, mtp_hidden), the last two as bits."""
    params, rows, _ = SCENES[scene]
    runner = ModelRunner(drafting(attention_backend="pallas"), params=params)
    assert runner.backends.interpret
    # As backends.choose decides on one TPU device, before a program is built.
    runner.backends = dataclasses.replace(runner.backends,
                                          kv_commit="in_place")
    pos = {}
    for slot, (prompt, pages, _cap) in rows.items():
        n = len(prompt)
        runner.prefill_batch([PrefillSeq(
            tokens=np.asarray(prompt, np.int32), start_pos=0,
            chunk_pages=pages[:-(-n // PAGE)], hist_pages=None,
            sampling=(0.0, 0, 1.0), next_page=int(pages[n // PAGE]))],
            slots=[slot])
        pos[slot] = n
    # What prefill left, then what each window leaves.
    seen = [(None, None, bits(runner.k_cache), bits(runner.mtp_hidden))]
    for _ in range(windows):
        packed = np.zeros((4, PK_PREFIX + 8), np.int32)
        for slot, (_prompt, pages, cap) in rows.items():
            packed[slot, PK_POS] = pos[slot]
            packed[slot, PK_SEQLEN] = pos[slot] + 1
            packed[slot, PK_CAP] = len(pages) * PAGE if cap is None else cap
            packed[slot, PK_PREFIX:PK_PREFIX + len(pages)] = pages
        out = runner.decode_window(packed, window)
        emit = np.asarray(out[4]["emit"])
        for slot in rows:
            pos[slot] += int(emit[:, slot].sum())
        seen.append((np.asarray(out[0]), emit, bits(runner.k_cache),
                     bits(runner.mtp_hidden)))
    return seen


@pytest.fixture(scope="module")
def both_writes():
    """scene -> (the kernel's three windows, the parent's write's), each
    served once a module."""
    from dynamo_tpu.engine import attention
    cache = {}

    def get(scene):
        if scene not in cache:
            ours = drafted(scene)
            with pytest.MonkeyPatch.context() as patch:
                patch.setattr(attention, "write_window_rows_pallas",
                              parent_s_write)
                cache[scene] = (ours, drafted(scene))
        return cache[scene]
    return get


@pytest.mark.parametrize("windows", [1, 3])
@pytest.mark.parametrize("scene", list(SCENES))
def test_a_drafting_window_leaves_what_the_parent_s_write_left(
        both_writes, scene, windows):
    """After one window and after three, the pool's entries of the model's
    layers and of the module's layer, ``mtp_hidden`` and the tokens are bit
    for bit what the same program leaves with ``dynamic_update_slice`` as
    its step write: nothing but who writes differs between the two."""
    ours, parents = both_writes(scene)
    emits = np.concatenate([w[1] for w in ours[1:]])
    assert SCENES[scene][2](emits), emits.T
    for (toks, emit, pool, hidden), (toks_p, emit_p, pool_p, hidden_p) in zip(
            ours[1:1 + windows], parents[1:]):
        assert (emit == emit_p).all() and (toks == toks_p).all()
        assert (pool == pool_p).all() and (hidden == hidden_p).all()
    # The windows wrote: the model's layers and the module's both moved.
    moved = ours[windows][2] != ours[0][2]
    assert moved[:SPEC.num_layers].any() and moved[SPEC.num_layers].any()


# -- through the engine -----------------------------------------------------------

@async_test
async def test_the_engine_serves_drafting_as_it_serves_plain():
    """Greedy output through the engine's emit walk (0, 1 or 2 tokens a
    row-step, pipelined windows, data-dependent positions) is the plain
    engine's token for token, drafts are both accepted and rejected, the
    counters and max_tokens hold, and logprobs are served."""
    plain = TPUEngine(config(), params=PARAMS)
    spec = TPUEngine(drafting(), params=PARAMS)
    plain.start()
    spec.start()
    try:
        total = 0
        for seed, n, cap in ((1, 19, 41), (2, 33, 60), (3, 48, 37),
                             (4, 16, 64)):
            prompt = prompt_of(n, seed)
            want, want_lp, _ = await collect(plain, prompt, cap, logprobs=1)
            got, got_lp, finish = await collect(spec, prompt, cap, logprobs=1)
            assert len(got) == cap and finish == "length"
            n = same_up_to_a_tie(prompt, want, got)
            assert len(got_lp) == cap and close(got_lp, want_lp, n)
            assert close(got_lp, ref.reference_logprobs(PARAMS, SPEC, prompt,
                                                        got))
            total += cap
        assert total == 202 and spec.tokens_generated_total \
            == plain.tokens_generated_total == total - 4   # less the firsts
        assert 0 < spec.spec_accepted < spec.spec_tokens
        assert sum(e * n for e, n in enumerate(spec.spec_emit_hist)) \
            >= total - 4        # the prefill's first tokens are not steps
        status = spec.perf_status()
        assert status["draft"] == "mtp" and plain.perf_status()[
            "draft"] == "none"
        assert status["spec"]["draft"] == "mtp"
        labels = status["compiles"]["programs"]["decode_window"]["labels"]
        # (One registry a process: the plain engine's label is beside it.)
        assert "mtp" in np.atleast_1d(labels["draft"])
    finally:
        plain.stop()
        spec.stop()


@async_test
async def test_drafting_rows_in_a_batch_and_a_long_prompt_in_chunks():
    """Several rows at once, one of them past the chunk budget (prefill in
    chunks over history fills the module's entries across chunk borders):
    each row's output is what the plain engine gives it alone."""
    plain = TPUEngine(config(), params=PARAMS)
    spec = TPUEngine(drafting(), params=PARAMS)
    plain.start()
    spec.start()
    try:
        import asyncio
        prompts = [prompt_of(n, 20 + n) for n in (100, 17, 40)]
        want = [(await collect(plain, p, 30))[0] for p in prompts]
        got = await asyncio.gather(*(collect(spec, p, 30, logprobs=1)
                                     for p in prompts))
        for prompt, a, (b, lps, _) in zip(prompts, want, got):
            # The plain engine's tokens up to a verified near-tie; past it
            # the reference's verdict on what was served.
            same_up_to_a_tie(prompt, a, b)
            assert len(b) == 30 and close(lps, ref.reference_logprobs(
                PARAMS, SPEC, prompt, b))
        assert spec.chunk_dispatch_count > 0
    finally:
        plain.stop()
        spec.stop()


@async_test
async def test_penalties_are_refused_and_logprobs_are_not():
    spec = TPUEngine(drafting(), params=PARAMS)
    spec.start()
    try:
        req = PreprocessedRequest(model="m", token_ids=prompt_of(8, 1))
        req.sampling_options.frequency_penalty = 0.5
        with pytest.raises(ValueError, match="penalties"):
            async for _ in spec.generate(req, Context()):
                pass
    finally:
        spec.stop()


# -- the share ---------------------------------------------------------------------

def test_the_four_shares_add_up_to_the_uncut_layer():
    """The routed parts of all four shares, with attention and ONE shared
    expert counted once, are what the uncut reference gives for the whole
    layer; and the program's block over one share is that share's part."""
    whole = read_spec({**TOY, "n_routed_experts": 16,
                       "expert_parallel": None})
    params = model.init_params(whole, jax.random.key(5))
    params["layers"]["moe_gate"] = params["layers"]["moe_gate"] * 8.0
    shares = []
    for first in (0, 4, 8, 12):
        spec = dataclasses.replace(whole, num_experts=4, first_expert=first)
        layers = dict(params["layers"])
        for key in ("moe_w_gate", "moe_w_up", "moe_w_down"):
            layers[key] = layers[key][:, first:first + 4]
        shares.append((spec, {**params, "layers": layers}))
    n = 24
    x = jax.random.normal(jax.random.key(9), (n, whole.hidden_size))
    x = x.astype(jnp.bfloat16).astype(jnp.float32)
    index = whole.first_k_dense
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
