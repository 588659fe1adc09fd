"""The two kernels of engine/experts.py against the masked product over
every resident expert: the grouped one (sorted (row, choice) pairs, each
multiplied by its own expert as stored) and the one a window's step takes
(every row by the experts the live rows chose, one visit an expert); they
run interpreted here, as the attention kernels' tests run theirs."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import experts, model
from dynamo_tpu.engine.backends import XLA, Backends
from dynamo_tpu.engine.config import Cohere2MoeSpec, EngineConfig, ModelSpec
from dynamo_tpu.engine.quant import QTensor, quantize_params

#: A runner's record on one CPU device, as the expert layer reads it.
WHOLE = Backends(experts_whole=True, interpret=True)

CONFIGS = os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs")
H, I = 128, 256
_ATTN = dict(hidden_size=H, intermediate_size=I, moe_intermediate_size=I,
             num_layers=1, num_heads=2, num_kv_heads=1, head_dim=64)
#: Every expert held, and experts 16 to 31 of a router over 64.
HELD = {"all held": dict(num_experts=16, num_experts_per_tok=4),
        "a share": dict(num_experts=16, num_experts_per_tok=4,
                        num_routed_experts=64, first_expert=16)}
#: ReGLU, SwiGLU, and a two-matrix expert (no gate leaf) whose width is
#: one lane tile and three quarters.
ACTS = ("relu", "silu", "relu2")
WIDTH = {"relu2": 224}


def layer(spec: ModelSpec, quant: bool, seed: int = 0) -> dict:
    e, ks = spec.num_experts, jax.random.split(jax.random.key(seed), 4)
    I = spec.expert_size  # noqa: E741,N806

    def w(k, shape):
        a = jax.random.normal(k, shape, jnp.float32) * shape[-2] ** -0.5
        if not quant:
            return a.astype(jnp.bfloat16)
        s = jnp.max(jnp.abs(a), axis=-2, keepdims=True) / 127
        return QTensor(jnp.round(a / s).astype(jnp.int8), s)

    lp = {"moe_gate": jnp.zeros((H, spec.router_width), jnp.bfloat16),
          "moe_w_gate": w(ks[0], (e, H, I)), "moe_w_up": w(ks[1], (e, H, I)),
          "moe_w_down": w(ks[2], (e, I, H))}
    if spec.ffn_act == "relu2":
        del lp["moe_w_gate"]
    return lp


def skewed(spec: ModelSpec, rows: int):
    """A routing with an expert nobody chose and one that fills a row tile
    and more: every row's first choice is the first expert held (``rows``
    pairs, over ROW_TILE), the last expert held gets none, the rest spread
    over the router's width."""
    r, k = spec.router_width, spec.num_experts_per_tok
    first, last = spec.first_expert, spec.first_expert + spec.num_experts - 1
    others = np.asarray([e for e in range(r) if e not in (first, last)])
    t = np.arange(rows)[:, None]
    rest = others[(t * 7 + np.arange(k - 1)[None, :] * 13) % len(others)]
    top_i = np.concatenate([np.full((rows, 1), first), rest], axis=1)
    gates = np.random.default_rng(rows).uniform(0.1, 1.0, top_i.shape)

    def route(router, spec, bias=None):
        return (jnp.asarray(gates / gates.sum(-1, keepdims=True),
                            jnp.float32), jnp.asarray(top_i, jnp.int32))
    return route, top_i


@pytest.mark.parametrize("rows", [128, 264, 520])
@pytest.mark.parametrize("act", ACTS)
@pytest.mark.parametrize("quant", [True, False], ids=["int8", "bf16"])
@pytest.mark.parametrize("held", list(HELD))
def test_the_kernel_gives_the_masked_product(held, quant, act, rows,
                                             monkeypatch):
    spec = Cohere2MoeSpec(**{**_ATTN, "moe_intermediate_size": WIDTH.get(
        act, I)}, **HELD[held], ffn_act=act)
    assert spec.holds_share == (held == "a share")
    lp = layer(spec, quant)
    route, top_i = skewed(spec, rows)
    monkeypatch.setattr(model, "moe_route", route)
    local = top_i - spec.first_expert
    load = np.bincount(local[(local >= 0) & (local < 16)], minlength=16)
    assert load[-1] == 0 and load[0] == rows >= experts.ROW_TILE
    x = jax.random.normal(jax.random.key(rows), (rows, H), jnp.bfloat16)
    outs = {}
    monkeypatch.setattr(model, "MOE_DENSE_MAX_ROWS", 64)
    for product, record in (("grouped", WHOLE), ("masked", XLA)):
        assert model.expert_product(rows, record) == product
        outs[product] = np.asarray(jax.jit(lambda x: model.ffn_block(
            x, lp, spec, router_in=x, backends=record))(x),
            np.float32)
    assert np.abs(outs["masked"]).mean() > 0.05
    np.testing.assert_allclose(outs["grouped"], outs["masked"], atol=0.05)


def test_a_share_whose_every_pair_falls_elsewhere_gives_zeros(monkeypatch):
    spec = Cohere2MoeSpec(**_ATTN, **HELD["a share"])
    lp = layer(spec, True)
    rows = 136
    top_i = jnp.broadcast_to(jnp.asarray([0, 5, 40, 63]), (rows, 4))
    monkeypatch.setattr(model, "moe_route", lambda *a: (
        jnp.full(top_i.shape, 0.25, jnp.float32), top_i))
    monkeypatch.setattr(model, "MOE_DENSE_MAX_ROWS", 64)
    x = jax.random.normal(jax.random.key(1), (rows, H), jnp.bfloat16)
    out = jax.jit(lambda x: model.ffn_block(
        x, lp, spec, backends=WHOLE))(x)
    assert out.shape == x.shape and not np.asarray(out, np.float32).any()


def test_the_walk_visits_each_group_on_each_of_its_row_tiles():
    sizes = jnp.asarray([0, 130, 0, 126, 1, 0, 300, 0], jnp.int32)
    offsets, group, tile, count = experts.visits(sizes, 6 * 128)
    assert offsets.tolist() == [0, 0, 130, 130, 256, 257, 257, 557, 557]
    n = int(count)
    # 130 rows over two tiles, 126 inside the second, one row alone in the
    # third, 300 from the third to the fifth; the sixth tile holds nobody.
    assert list(zip(group[:n].tolist(), tile[:n].tolist())) == [
        (1, 0), (1, 1), (3, 1), (4, 2), (6, 2), (6, 3), (6, 4)]
    assert group.shape == tile.shape == (6 + 8 - 1,)
    assert int(experts.visits(jnp.zeros(8, jnp.int32), 6 * 128)[3]) == 0
    assert experts.out_tile(2560, 768) == 768
    assert experts.out_tile(768, 2560) == 2560
    assert experts.out_tile(4096, 4096) == 512
    assert experts.out_tile(64, 48) == 48


# -- a window's step: the experts its live rows chose ---------------------------

#: How many of a step's rows are live: every row, 19 of 32 (the cells'
#: share of their slots), one, none.
LIVE = {"every row live": lambda rows: rows,
        "19 of 32": lambda rows: rows * 19 // 32,
        "one": lambda rows: 1, "none": lambda rows: 0}
_STEP = {}


def stepped(spec: ModelSpec, rows: int):
    """A routing under which later rows choose later experts: row t takes
    the k experts from the (t // (rows / 8))-th held on, so the rows that
    are not live choose experts no live row chose, and the last five held
    are nobody's; a share's last choice falls on an expert held elsewhere."""
    k, first = spec.num_experts_per_tok, spec.first_expert
    t = np.arange(rows)
    top_i = first + (t // max(rows // 8, 1))[:, None] + np.arange(k)[None, :]
    if spec.holds_share:
        top_i[:, -1] = first + spec.num_experts + t % 16
    gates = np.random.default_rng(rows).uniform(0.1, 1.0, top_i.shape)

    def route(router, spec, bias=None):
        return (jnp.asarray(gates / gates.sum(-1, keepdims=True),
                            jnp.float32), jnp.asarray(top_i, jnp.int32))
    return route, top_i


def _step(held, quant, act, rows, product, monkeypatch):
    """(spec, clean leaves, the routing's choices, jitted ``ffn_block`` of
    (x, leaves, live)) of a case, one trace a (shape, product)."""
    spec = Cohere2MoeSpec(**{**_ATTN, "moe_intermediate_size": WIDTH.get(
        act, I)}, **HELD[held], ffn_act=act)
    route, top_i = stepped(spec, rows)
    monkeypatch.setattr(model, "moe_route", route)
    record = WHOLE if product == "touched" else XLA
    assert model.expert_product(rows, record) == product
    key = (held, quant, act, rows, product)
    if key not in _STEP:
        _STEP[key] = jax.jit(lambda x, lp, live: model.ffn_block(
            x, lp, spec, router_in=x, live=live, backends=record))
    return spec, layer(spec, quant), top_i, _STEP[key]


def poisoned(lp: dict, spare: np.ndarray) -> dict:
    """The leaves with every expert in ``spare`` (bool [E]) unreadable: a
    NaN in its scales (int8) or its weights. A product that reads such an
    expert at all returns NaN in every row (0 x NaN)."""
    def spoil(w):
        if isinstance(w, QTensor):
            return QTensor(w.q, jnp.where(spare[:, None, None], jnp.nan, w.s))
        return jnp.where(spare[:, None, None], jnp.nan, w)
    return {k: spoil(v) if k.startswith("moe_w_") else v
            for k, v in lp.items()}


@pytest.mark.parametrize("live", list(LIVE))
@pytest.mark.parametrize("rows", [8, 32, 64])
@pytest.mark.parametrize("act", ACTS)
@pytest.mark.parametrize("quant", [True, False], ids=["int8", "bf16"])
@pytest.mark.parametrize("held", list(HELD))
def test_a_step_walks_the_experts_its_live_rows_chose(held, quant, act, rows,
                                                      live, monkeypatch):
    """``experts.touched_product`` against the masked product: the live
    rows' outputs agree, a row that is not live comes back zero, the visits
    are ``moe_load_stats``' distinct count, and an expert no LIVE row chose
    is never read: its leaves hold NaN here (a dead slot's choices fall on
    such experts too), under which the masked product returns nothing but
    NaN. With no live row the kernel runs no step and returns zeros."""
    n = LIVE[live](rows)
    alive = np.arange(rows) < n
    spec, lp, top_i, walk = _step(held, quant, act, rows, "touched",
                                  monkeypatch)
    local = top_i[alive] - spec.first_expert
    chosen = np.unique(local[(local >= 0) & (local < spec.num_experts)])
    spare = ~np.isin(np.arange(spec.num_experts), chosen)
    dead = top_i[~alive] - spec.first_expert
    assert spare.sum() >= 5
    if n < rows:    # the dead slots chose experts no live row did
        assert np.isin(dead, np.flatnonzero(spare)).any()
    x = jax.random.normal(jax.random.key(rows), (rows, H), jnp.bfloat16)
    out, stats = walk(x, poisoned(lp, spare), jnp.asarray(alive))
    out = np.asarray(out, np.float32)
    assert stats[0] == len(chosen) and stats[2] == (n > 0)
    _, order, count = model.held_load(jax.nn.one_hot(
        top_i - spec.first_expert, spec.num_experts), jnp.asarray(alive))
    assert int(count) == len(chosen)
    assert order[:len(chosen)].tolist() == chosen.tolist()
    assert np.isfinite(out).all() and not out[~alive].any()
    spec, lp, _, masked = _step(held, quant, act, rows, "masked", monkeypatch)
    want, want_stats = masked(x, lp, jnp.asarray(alive))
    np.testing.assert_array_equal(stats, want_stats)
    if n:
        want = np.asarray(want, np.float32)[alive]
        assert np.abs(want).mean() > 0.05
        np.testing.assert_allclose(out[alive], want, atol=0.05)
        assert np.isnan(np.asarray(masked(x, poisoned(lp, spare), jnp.asarray(
            alive))[0], np.float32)).all() or not spare.any()


def decided(params):
    """A router whose choices are decided (logits an order apart): a
    rounding of one product must not route a later layer's row elsewhere
    under the other."""
    params["layers"]["moe_gate"] = params["layers"]["moe_gate"] * 8.0
    return params


def rehearsal(name: str | None):
    """A benchmark configuration's rehearsal model, int8 as its cell
    launches it; None: a Mixtral-style router over bfloat16 leaves."""
    if name is None:
        spec = ModelSpec(vocab_size=64, hidden_size=32, intermediate_size=16,
                         num_layers=2, num_heads=2, num_kv_heads=1,
                         num_experts=4, num_experts_per_tok=2)
        return spec, decided(model.init_params(spec, jax.random.key(0)))
    if name == "a share of two-matrix experts":
        # relu2 experts 4 to 7 of a router over 16, 40 wide (no gate leaf,
        # no whole number of lane tiles), int8, beside a shared expert.
        spec = Cohere2MoeSpec(
            vocab_size=64, hidden_size=128, intermediate_size=40,
            moe_intermediate_size=40, num_layers=3, num_heads=2,
            num_kv_heads=1, head_dim=64, num_experts=4,
            num_experts_per_tok=3, num_routed_experts=16, first_expert=4,
            num_shared_experts=1, ffn_act="relu2", quant="int8")
        params = decided(model.init_params(spec, jax.random.key(5)))
        assert "moe_w_gate" not in params["layers"]
        return spec, jax.tree.map(jnp.asarray, quantize_params(
            jax.tree.map(np.asarray, params)))
    import tempfile
    with open(os.path.join(CONFIGS, name + ".json"), encoding="utf-8") as fh:
        config = json.load(fh)
    config.update(config.pop("rehearsal_model"))
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "config.json")
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(config, fh)
        spec = dataclasses.replace(ModelSpec.from_hf_config(path),
                                   quant="int8")
    params = decided(model.init_params(spec, jax.random.key(3)))
    return spec, jax.tree.map(jnp.asarray, quantize_params(
        jax.tree.map(np.asarray, params)))


@pytest.mark.parametrize("product", ["grouped", "touched"])
@pytest.mark.parametrize("name", [
    "smallthinker-21b-a3b-int8", "command-a-plus-ep8-int8",
    "deepseek-v3.2-exp-ep16-int8", "glm-4.7-flash-ep4-int8", None,
    "a share of two-matrix experts"])
def test_prefill_gives_the_masked_products_logits(name, product, monkeypatch):
    """``prefill_forward`` of each routed rehearsal model with the prompt's
    rows on either side of MOE_DENSE_MAX_ROWS (over it the grouped product;
    within it the walk, every row live): the masked product's logits (the
    record of a mesh: XLA's)."""
    spec, params = rehearsal(name)
    b, s, page = 2, 16, 4
    heads, (dk, dv) = spec.kv_entry
    shape = (spec.num_layers + spec.mtp_layers, heads, b * s // page + 1, page)
    pools = (jnp.zeros((*shape, dk), jnp.bfloat16),
             jnp.zeros((*shape, dv), jnp.bfloat16))
    tokens = np.asarray(jax.random.randint(
        jax.random.key(4), (b, s), 1, spec.vocab_size), np.int32)
    pos = np.broadcast_to(np.arange(s, dtype=np.int32), (b, s))
    table = (1 + np.arange(b * s // page, dtype=np.int32)).reshape(b, -1)
    monkeypatch.setattr(model, "MOE_DENSE_MAX_ROWS",
                        b * s - (product == "grouped"))
    logits = {}
    for taken, record in ((product, WHOLE), ("masked", XLA)):
        assert model.expert_product(b * s, record) == taken
        logits[taken] = np.asarray(jax.jit(
            lambda p, k, v: model.prefill_forward(
                p, spec, k, v, tokens, pos, table, np.full((b,), s, np.int32),
                backends=record)[0])(params, *pools), np.float32)
    assert np.abs(logits["masked"]).mean() > 0.05
    # Eight layers deep, two roundings of each layer's gate and up apart
    # (the masked product rounds a product to bfloat16 before its scale).
    np.testing.assert_allclose(logits[product], logits["masked"],
                               atol=0.1, rtol=0.05)


def test_a_chunk_over_history_reads_the_expert_stacks_whole(monkeypatch):
    """The runner's with-history prefill (a prompt longer than a bucket) on
    either side of the threshold: its layer scan hands the kernel the
    expert stacks whole (``scan_layers(whole_experts=True)``), which changes
    WHERE the kernel reads and nothing else: the logits are those of the
    same kernel over experts sliced a layer, bit for bit; the walk's are the
    grouped product's at this toy's width (one arithmetic), and the masked
    product's (a runner told its experts may be partitioned) to two chunks'
    roundings."""
    spec, params = rehearsal("smallthinker-21b-a3b-int8")
    tokens = np.arange(1, 31, dtype=np.int32)
    real, handed = model.scan_layers, []

    def sliced(*a, whole_experts=False, **kw):
        handed.append(whole_experts)
        return real(*a, **kw)

    logits = {}
    for name, product, limit, scan in (
            ("whole", "grouped", 8, real), ("sliced", "grouped", 8, sliced),
            ("walked", "touched", 16, real), ("masked", "masked", 16, real)):
        monkeypatch.setattr(model, "MOE_DENSE_MAX_ROWS", limit)
        monkeypatch.setattr(model, "scan_layers", scan)
        runner = _runner(spec, params)
        if product == "masked":
            runner.backends = dataclasses.replace(runner.backends,
                                                  experts_whole=False)
        runner.prefill(tokens[:16], 0, np.arange(1, 5), None, (0.0, 0, 1.0))
        _, out = runner.prefill(tokens[16:], 16, np.arange(5, 9),
                                np.arange(1, 5), (0.0, 0, 1.0))
        assert {fn._labels["expert_product"] for fn
                in runner._prefill_cache.values()} == {product}
        logits[name] = np.asarray(out, np.float32)
    assert handed == [True, True]       # both programs asked for the stacks
    np.testing.assert_array_equal(logits["whole"], logits["sliced"])
    np.testing.assert_array_equal(logits["whole"], logits["walked"])
    assert np.abs(logits["masked"]).mean() > 0.3
    np.testing.assert_allclose(logits["whole"], logits["masked"], atol=0.25)


# -- the label says what ran ----------------------------------------------------

def _runner(spec, params, **kw):
    from dynamo_tpu.engine.runner import ModelRunner
    return ModelRunner(EngineConfig(
        model=spec, page_size=4, num_pages=64, max_pages_per_seq=16,
        max_num_seqs=2, prefill_buckets=(16, 32), attention_backend="xla",
        **kw), params=params)


def test_the_label_says_which_product_a_program_takes(monkeypatch):
    from dynamo_tpu.engine import perf
    from dynamo_tpu.engine.runner import PrefillSeq
    monkeypatch.setattr(model, "MOE_DENSE_MAX_ROWS", 16)
    perf.get_registry().reset()
    spec, params = rehearsal(None)
    runner = _runner(spec, params)
    assert runner.backends.experts_whole and runner.backends.interpret
    seqs = [PrefillSeq(tokens=np.arange(1, 1 + n, dtype=np.int32),
                       start_pos=0, hist_pages=None, sampling=(0.0, 0, 1.0),
                       chunk_pages=np.arange(1 + 8 * i, 9 + 8 * i))
            for i, n in enumerate((20, 30))]
    runner.prefill_batch(seqs[:1])      # 1 x 32 rows: over the threshold
    assert runner.moe_grouped_pairs == 32 * spec.num_experts_per_tok
    runner.prefill_batch(seqs)          # 2 x 32 rows
    assert runner.moe_grouped_pairs == 96 * spec.num_experts_per_tok
    runner.prefill_batch([dataclasses.replace(
        seqs[0], tokens=seqs[0].tokens[:10],
        chunk_pages=seqs[0].chunk_pages[:4])])                  # 16 rows
    assert runner.moe_grouped_pairs == 96 * spec.num_experts_per_tok
    labels = {key: fn._labels for key, fn in runner._prefill_cache.items()}
    assert {key[:2]: v for key, v in labels.items()} == {
        (32, 1): {"expert_product": "grouped"},
        (32, 2): {"expert_product": "grouped"},
        (16, 1): {"expert_product": "touched"}}
    window = runner._get_window(2, 4)
    assert window._labels["expert_product"] == "touched"  # 2 rows a step
    assert perf.get_registry().label_values("expert_product") == {
        "prefill": ["grouped", "touched"], "decode_window": ["touched"]}
    # Any mesh keeps the masked product at every size.
    meshed = _runner(spec, params, tp=2)
    assert not meshed.backends.experts_whole
    assert meshed._get_window(2, 4)._labels["expert_product"] == "masked"
    assert {model.expert_product(rows, meshed.backends)
            for rows in (2, 32, 64, 128, 4096)} == {"masked"}
    # A dense block has no such label.
    perf.get_registry().reset()
    dense = ModelSpec(vocab_size=64, hidden_size=32, intermediate_size=16,
                      num_layers=1, num_heads=2, num_kv_heads=1)
    runner = _runner(dense, model.init_params(dense, jax.random.key(0)))
    runner.prefill_batch(seqs[:1])
    assert runner.moe_grouped_pairs == 0
    assert all("expert_product" not in fn._labels
               for fn in runner._prefill_cache.values())
    assert "expert_product" not in runner._get_window(2, 4)._labels


# -- the window programs take the walk --------------------------------------------

#: block kind -> (rehearsal model, what its cell's launch adds).
WINDOWS = {"dense block": ("smallthinker-21b-a3b-int8", {}),
           "hybrid": ("nemotron-3-nano-30b-a3b-ep4-int8", {}),
           "delta rule": ("solar-open2-250b-ep8-int8", {}),
           "drafting": ("glm-4.7-flash-ep4-int8",
                        dict(spec_decode="mtp", spec_k=1))}


def _windows(name, launch, dead_token=None, windows=2, steps=4, whole=True):
    """Two prompts into slots 0 and 2 of four, then ``windows`` windows over
    them; the slots between hold ``dead_token`` at a position of their own
    where it is given (and no sequence: they are not live). ``whole``
    False: a runner told that its experts may be partitioned (the masked
    product). Returns the runner and, a live slot, its emitted tokens and
    their logprobs."""
    from dynamo_tpu.engine.runner import (PK_CAP, PK_LOGPROB, PK_OVERRIDE,
                                          PK_POS, PK_PREFIX, PK_SEQLEN,
                                          PK_TOKEN, PK_TOPP, ModelRunner,
                                          PrefillSeq)
    spec, params = rehearsal(name)
    page = 4
    runner = ModelRunner(EngineConfig(
        model=spec, page_size=page, num_pages=64, max_pages_per_seq=8,
        max_num_seqs=4, prefill_buckets=(16, 32), attention_backend="xla",
        decode_window=steps, **launch), params=params)
    runner.backends = dataclasses.replace(runner.backends,
                                          experts_whole=whole)
    prompts = {0: (np.arange(3, 14) * 5) % spec.vocab_size,
               2: (np.arange(2, 9) * 7) % spec.vocab_size}
    pages = {0: np.arange(1, 9, dtype=np.int32),
             2: np.arange(9, 17, dtype=np.int32)}
    seqs = [PrefillSeq(tokens=np.asarray(p, np.int32), start_pos=0,
                       chunk_pages=pages[s][:-(-len(p) // page)],
                       hist_pages=None, sampling=(0.0, 0, 1.0),
                       next_page=int(pages[s][len(p) // page]))
            for s, p in prompts.items()]
    runner.prefill_batch(seqs, slots=list(prompts))
    pos = {s: len(p) for s, p in prompts.items()}
    toks, lps = {s: [] for s in prompts}, {s: [] for s in prompts}
    for _ in range(windows):
        packed = np.zeros((4, PK_PREFIX + 8), np.int32)
        packed[:, PK_TOPP] = np.float32(1.0).view(np.int32)
        for s in prompts:
            packed[s, PK_POS], packed[s, PK_SEQLEN] = pos[s], pos[s] + 1
            packed[s, PK_CAP], packed[s, PK_LOGPROB] = 8 * page, 1
            packed[s, PK_PREFIX:] = pages[s]
        if dead_token is not None:
            for s in (1, 3):
                packed[s, PK_OVERRIDE], packed[s, PK_TOKEN] = 1, dead_token
                packed[s, PK_POS], packed[s, PK_CAP] = 5 + s, 8 * page
                packed[s, PK_PREFIX:] = 17 + 8 * (s // 2) + np.arange(8)
        out = runner.decode_window(packed, steps)
        t, lp = np.asarray(out[0]), np.asarray(out[1])
        for s in prompts:
            if "spec_decode" not in launch:
                toks[s] += t[:, s].tolist()
                lps[s] += lp[:, s].tolist()
                pos[s] += steps
                continue
            for m, e in enumerate(np.asarray(out[4]["emit"])[:, s]):
                toks[s] += t[m, s, :e].tolist()
                lps[s] += lp[m, s, :e].tolist()
                pos[s] += int(e)
    return runner, toks, lps


# (The delta rule's rehearsal model carries a rounding far, sixteen
# sublayers behind a sharpened router: its streams part from the masked
# run's at the third token. It is held to its own experts, to the label and
# to the dead slots below, not to logprobs.)
@pytest.mark.parametrize("kind", ["dense block", "hybrid", "drafting"])
def test_a_window_program_takes_the_walk_and_gives_the_masked_logprobs(
        kind, monkeypatch):
    """The window program of each routed rehearsal model (the dense block's
    scan, the hybrid's groups, the drafting window's verify step and its
    module's own expert layer) with two of four slots live: labelled
    ``touched``, its emitted tokens are the masked run's up to a tie and
    their logprobs agree as the grouped product's logits do; every expert layer's stacks reach
    ONE kernel call whole (no ``[None]`` of a layer's slice but the
    module's, a stack of one)."""
    from dynamo_tpu.engine import experts
    name, launch = WINDOWS[kind]
    calls = []
    real = experts.touched_product
    monkeypatch.setattr(experts, "touched_product", lambda x, g, ws, *a, **kw: (
        calls.append(ws[0].shape[0]) or real(x, g, ws, *a, **kw)))
    got = {}
    for product in ("touched", "masked"):
        calls.clear()
        runner, toks, lps = _windows(name, launch,
                                     whole=product == "touched")
        label, = {fn._labels["expert_product"]
                  for fn in runner._window_cache.values()}
        assert label == product
        got[product] = toks, lps
        if product == "masked":
            assert not calls
            continue
        layers = runner.spec.num_layers - runner.spec.first_k_dense
        if runner.spec.recurrent:
            layers = sum(c == "E" for c in runner.spec.layer_pattern) or len(
                [c for c in runner.spec.layer_pattern if c == "*"]) or layers
        # Traced once a scan (a prefill program of 16 rows takes the walk
        # too): each call sees the stack over all its layers.
        assert calls and set(calls) <= {layers, 1}, (calls, layers)
    for slot in (0, 2):
        (a, la), (b, lb) = ((got[p][0][slot], got[p][1][slot])
                            for p in ("touched", "masked"))
        # Greedy over random weights: where the two part it is on a tie
        # (the tokens differ, their logprobs do not), and what follows is
        # another sequence.
        n = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), len(a))
        assert len(a) >= 8 and n >= 4, (a, b)
        np.testing.assert_allclose(la[:n + 1], lb[:n + 1], atol=0.1,
                                   rtol=0.05)


@pytest.mark.parametrize("product", ["grouped", "touched"])
def test_a_group_without_a_mixer_reads_its_own_experts(product, monkeypatch):
    """The delta-rule block's first group is attention and an expert layer,
    no mixer: the index its scan carries for the mixers' stacks is NOT the
    group's, and the expert stacks handed whole are read by the group's own
    (hybrid.scan_groups): the logits are those of the same kernel over
    experts sliced a group, bit for bit. Until PR 56 the stacks were read by
    the mixers' index: a prompt over MOE_DENSE_MAX_ROWS rows multiplied
    group g by the experts of group g - 1 (no cell's check sent such a
    prompt; a window step takes a kernel since PR 56, and the cell's check
    then read 0.23 nat at the median against 0.07 allowed)."""
    from dynamo_tpu.engine import hybrid
    from dynamo_tpu.engine.runner import ModelRunner, PrefillSeq
    spec, params = rehearsal(WINDOWS["delta rule"][0])
    assert spec.layer_pattern[0] == "*" and "K" in spec.layer_pattern
    monkeypatch.setattr(model, "MOE_DENSE_MAX_ROWS",
                        8 if product == "grouped" else 128)
    tokens = (np.arange(3, 17) * 5) % spec.vocab_size
    real, logits = hybrid.whole_expert_leaves, {}
    for name, whole, leaves in (("whole", True, real),
                                ("sliced", True, lambda ffn: (ffn, {})),
                                ("masked", False, real)):
        monkeypatch.setattr(hybrid, "whole_expert_leaves", leaves)
        runner = ModelRunner(EngineConfig(
            model=spec, page_size=4, num_pages=64, max_pages_per_seq=8,
            max_num_seqs=4, prefill_buckets=(16, 32),
            attention_backend="xla"), params=params)
        runner.backends = dataclasses.replace(runner.backends,
                                              experts_whole=whole)
        runner.prefill_batch([PrefillSeq(
            tokens=np.asarray(tokens, np.int32), start_pos=0,
            chunk_pages=np.arange(1, 5, dtype=np.int32), hist_pages=None,
            sampling=(0.0, 0, 1.0))], slots=[1])
        label, = {fn._labels["expert_product"]
                  for fn in runner._prefill_cache.values()}
        assert label == (product if whole else "masked")
        logits[name] = np.asarray(runner.last_prefill_logits, np.float32)[0]
    np.testing.assert_array_equal(logits["whole"], logits["sliced"])
    assert np.abs(logits["masked"]).max() > 1.0
    np.testing.assert_allclose(logits["whole"], logits["masked"], atol=0.3)


@pytest.mark.parametrize("kind", list(WINDOWS))
def test_a_live_row_does_not_depend_on_what_dead_slots_hold(kind):
    """Two runs of the same windows whose dead slots hold other tokens at
    other positions: the live rows' tokens and logprobs, the pool (K, V or
    the latent entries) and the recurrent state of the live slots are the
    same bit for bit. A dead slot chooses experts too; under the walk its
    choices make no visit and move no live row's sum."""
    name, launch = WINDOWS[kind]
    runs = [_windows(name, launch, dead_token=t) for t in (3, 41)]
    assert {fn._labels["expert_product"] for r, _, _ in runs
            for fn in r._window_cache.values()} == {"touched"}
    (a, ta, la), (b, tb, lb) = runs
    assert ta == tb and la == lb
    for pool in ("k_cache", "v_cache"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, pool)[:, :, 1:17], np.float32),
            np.asarray(getattr(b, pool)[:, :, 1:17], np.float32))
    # S [layers, slots, ...] and the carried inputs [layers, taps, slots, C].
    for x, y in zip(a.state_arrays, b.state_arrays):
        np.testing.assert_array_equal(*(
            np.take(np.asarray(s, np.float32), [0, 2], axis=s.ndim // 2 - 1)
            for s in (x, y)))
