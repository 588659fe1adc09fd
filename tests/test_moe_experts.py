"""The grouped expert product (engine/experts.py): sorted (row, choice)
pairs, each multiplied by its own expert as stored, against the masked
product over every resident expert; the kernel runs interpreted here, as
the attention kernels' tests run theirs."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import experts, model
from dynamo_tpu.engine.backends import Backends
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
    for product, limit in (("grouped", 64), ("masked", 10 ** 9)):
        monkeypatch.setattr(model, "MOE_DENSE_MAX_ROWS", limit)
        assert model.expert_product(rows, WHOLE) == product
        outs[product] = np.asarray(jax.jit(lambda x: model.ffn_block(
            x, lp, spec, router_in=x, backends=WHOLE))(x),
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


@pytest.mark.parametrize("name", [
    "smallthinker-21b-a3b-int8", "command-a-plus-ep8-int8",
    "deepseek-v3.2-exp-ep16-int8", "glm-4.7-flash-ep4-int8", None,
    "a share of two-matrix experts"])
def test_prefill_gives_the_masked_products_logits(name, monkeypatch):
    """``prefill_forward`` of each routed rehearsal model with the threshold
    on either side of the prompt's rows: the same logits."""
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
    logits = {}
    for product, limit in (("grouped", b * s - 1), ("masked", b * s)):
        monkeypatch.setattr(model, "MOE_DENSE_MAX_ROWS", limit)
        assert model.expert_product(b * s, WHOLE) == product
        logits[product] = np.asarray(jax.jit(
            lambda p, k, v: model.prefill_forward(
                p, spec, k, v, tokens, pos, table, np.full((b,), s, np.int32),
                backends=WHOLE)[0])(params, *pools), np.float32)
    assert np.abs(logits["masked"]).mean() > 0.05
    # Eight layers deep, two roundings of each layer's gate and up apart
    # (the masked product rounds a product to bfloat16 before its scale).
    np.testing.assert_allclose(logits["grouped"], logits["masked"],
                               atol=0.1, rtol=0.05)


def test_a_chunk_over_history_reads_the_expert_stacks_whole(monkeypatch):
    """The runner's with-history prefill (a prompt longer than a bucket)
    above the threshold: its layer scan hands the kernel the expert stacks
    whole (``scan_layers(whole_experts=True)``), which changes WHERE the
    kernel reads and nothing else: the logits are those of the same kernel
    over experts sliced a layer, bit for bit, and the masked product's to
    two chunks' roundings at this toy's width."""
    spec, params = rehearsal("smallthinker-21b-a3b-int8")
    tokens = np.arange(1, 31, dtype=np.int32)
    real, handed = model.scan_layers, []

    def sliced(*a, whole_experts=False, **kw):
        handed.append(whole_experts)
        return real(*a, **kw)

    logits = {}
    for name, limit, scan in (("whole", 8, real), ("sliced", 8, sliced),
                              ("masked", 10 ** 9, real)):
        monkeypatch.setattr(model, "MOE_DENSE_MAX_ROWS", limit)
        monkeypatch.setattr(model, "scan_layers", scan)
        runner = _runner(spec, params)
        runner.prefill(tokens[:16], 0, np.arange(1, 5), None, (0.0, 0, 1.0))
        _, out = runner.prefill(tokens[16:], 16, np.arange(5, 9),
                                np.arange(1, 5), (0.0, 0, 1.0))
        assert {fn._labels["expert_product"] for fn
                in runner._prefill_cache.values()} == {
                    "masked" if name == "masked" else "grouped"}
        logits[name] = np.asarray(out, np.float32)
    assert handed == [True, True]       # both programs asked for the stacks
    np.testing.assert_array_equal(logits["whole"], logits["sliced"])
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
        (16, 1): {"expert_product": "masked"}}
    window = runner._get_window(2, 4)
    assert window._labels["expert_product"] == "masked"   # 2 rows a step
    assert perf.get_registry().label_values("expert_product") == {
        "prefill": ["grouped", "masked"], "decode_window": ["masked"]}
    # Any mesh keeps the masked product at every size.
    assert not _runner(spec, params, tp=2).backends.experts_whole
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
