"""Pallas paged-attention kernel == XLA gather reference (VERDICT r2 #2).

The kernel wrapper no longer guesses: these CPU tests ask for the Pallas
interpreter explicitly (``interpret=True``); tests/test_tpu_compile.py
compiles the same kernels through Mosaic for a described v5e. Covers both kernel
layouts — D=64 (lane-packed, 2 tokens per 128-lane row) and D=128
(natural) — across ragged sequence lengths, GQA grouping, layer indexing
into the stacked cache, the deferred self-token column, and page-table
indirection. Tolerances are bf16-input flash-vs-softmax differences.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from dynamo_tpu.engine.attention import paged_decode_attention_pallas
from dynamo_tpu.engine.model import paged_decode_attention_xla


def _case(d, b, nkv, qpk, maxp, seq_lens, seed=0, page=16, L=2):
    rng = np.random.default_rng(seed)
    nh = nkv * qpk
    npages = maxp * b + 2
    q = jnp.asarray(rng.standard_normal((b, nh, d)), jnp.bfloat16)
    kc = jnp.asarray(rng.standard_normal((L, nkv, npages, page, d)),
                     jnp.bfloat16)
    vc = jnp.asarray(rng.standard_normal((L, nkv, npages, page, d)),
                     jnp.bfloat16)
    ks = jnp.asarray(rng.standard_normal((b, nkv, d)), jnp.bfloat16)
    vs = jnp.asarray(rng.standard_normal((b, nkv, d)), jnp.bfloat16)
    pt = np.zeros((b, maxp), np.int32)
    for i in range(b):
        pt[i] = rng.permutation(np.arange(1, npages - 1))[:maxp]
    sl = jnp.asarray(seq_lens, jnp.int32)
    return q, kc, vc, jnp.asarray(pt), sl, ks, vs


def _both(args, qpk, layer=1):
    q, kc, vc, pt, sl, ks, vs = args
    ly = jnp.asarray(layer, jnp.int32)
    ref = np.asarray(
        paged_decode_attention_xla(q, kc, vc, ly, pt, sl, ks, vs, qpk),
        np.float32)
    out = np.asarray(
        paged_decode_attention_pallas(q, kc, vc, ly, pt, sl, ks, vs, qpk,
                                      interpret=True),
        np.float32)
    return ref, out


@pytest.mark.parametrize("d", [64, 128])
def test_pallas_matches_xla(d):
    ref, out = _both(_case(d, b=4, nkv=2, qpk=4, maxp=8,
                           seq_lens=[5, 17, 64, 128]), qpk=4)
    np.testing.assert_allclose(out, ref, atol=0.03, rtol=0.03)


@pytest.mark.parametrize("d", [64, 128])
def test_pallas_matches_xla_long_ragged(d):
    """Lengths crossing multiple DMA chunks (chunk = 128 tokens), including
    zero-history (self-attention only) and non-chunk-aligned rows."""
    ref, out = _both(_case(d, b=4, nkv=2, qpk=2, maxp=32,
                           seq_lens=[0, 129, 300, 511], seed=3), qpk=2)
    np.testing.assert_allclose(out, ref, atol=0.03, rtol=0.03)


@pytest.mark.parametrize("layer", [0, 1])
def test_pallas_layer_indexing(layer):
    """The kernel must read the requested layer of the stacked cache."""
    args = _case(64, b=2, nkv=2, qpk=2, maxp=4, seq_lens=[30, 61], seed=4)
    ref, out = _both(args, qpk=2, layer=layer)
    np.testing.assert_allclose(out, ref, atol=0.03, rtol=0.03)
    # Cross-check: the two layers genuinely differ.
    other, _ = _both(args, qpk=2, layer=1 - layer)
    assert np.max(np.abs(ref - other)) > 0.01


def test_pallas_mqa_single_group():
    """MQA extreme: one KV head, 8 query heads."""
    ref, out = _both(_case(64, b=2, nkv=1, qpk=8, maxp=8,
                           seq_lens=[33, 90], seed=5), qpk=8)
    np.testing.assert_allclose(out, ref, atol=0.03, rtol=0.03)


def _window_both(d, nkv, qpk, maxp, lens, m, M=8, seed=11, L=2, page=16):
    """Window attention, kernel against the XLA gather, on a pool that
    holds only the live pages (a 256-page table stays a few MB)."""
    from dynamo_tpu.engine.attention import paged_window_attention_pallas
    from dynamo_tpu.engine.model import paged_window_attention_xla
    rng = np.random.default_rng(seed)
    b = len(lens)
    need = [-(-n // page) for n in lens]
    npages = sum(need) + 2
    ids = rng.permutation(np.arange(1, npages))
    pt = np.zeros((b, maxp), np.int32)
    at = 0
    for i, n in enumerate(need):
        pt[i, :n] = ids[at:at + n]
        at += n

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    args = (arr(b, nkv * qpk, d), arr(L, nkv, npages, page, d),
            arr(L, nkv, npages, page, d), jnp.asarray(1, jnp.int32),
            jnp.asarray(pt), jnp.asarray(lens, jnp.int32),
            arr(nkv, b, M, d), arr(nkv, b, M, d), jnp.asarray(m, jnp.int32),
            arr(b, nkv, d), arr(b, nkv, d))
    ref = paged_window_attention_xla(*args, qpk)
    out = paged_window_attention_pallas(*args, qpk, interpret=True)
    return np.asarray(ref, np.float32), np.asarray(out, np.float32)


@pytest.mark.parametrize("m", [0, 3])
def test_pallas_window_matches_xla(m):
    """Window variant: history kernel + in-window buffer cols (j < m) +
    self column must match the XLA window reference."""
    ref, out = _window_both(d=64, nkv=2, qpk=2, maxp=8,
                            lens=[0, 30, 64, 127], m=m, seed=7)
    np.testing.assert_allclose(out, ref, atol=0.03, rtol=0.03)


# Qwen2.5-7B, the benchmark cell's model: head_dim 128, 4 KV heads, 7 query
# heads per KV head (one 32-page chunk is 512 tokens at its widths).
QWEN_7B = dict(d=128, nkv=4, qpk=7)


#: 16, and the pages a derived page_size can be (config.resolve_page_size):
#: a 512-token chunk is 32, 16, 8 or 4 of them.
PAGES = [16, 32, 64, 128]


@pytest.mark.parametrize("page", PAGES)
def test_pallas_qwen7b_single_step(page):
    """The cell's head shape through the single decode step: lengths inside
    one chunk, at a chunk's edge and across three chunks."""
    ref, out = _both(_case(128, b=4, nkv=4, qpk=7, maxp=1280 // page,
                           seq_lens=[5, 512, 1100, 64], seed=8, page=page),
                     qpk=7)
    np.testing.assert_allclose(out, ref, atol=0.03, rtol=0.03)


@pytest.mark.parametrize("page", PAGES)
@pytest.mark.parametrize("m", [0, 3, 7])
def test_pallas_qwen7b_window(m, page):
    ref, out = _window_both(**QWEN_7B, maxp=1280 // page,
                            lens=[17, 513, 1100, 300], m=m, page=page)
    np.testing.assert_allclose(out, ref, atol=0.03, rtol=0.03)


@pytest.mark.parametrize("lens", [
    [0, 200, 0, 0, 513, 0, 31, 0],   # dead first, last and in runs
    [300, 0, 0, 0, 0, 0, 0, 40],     # the pipeline hops six dead slots
    [0, 0, 0, 0],                    # nobody has history: self column only
    [0, 0, 0, 77],                   # the first live row is the last row
], ids=["between", "hop", "all-dead", "last-only"])
@pytest.mark.parametrize("d", [64, 128])
def test_pallas_dead_slots_between_live_rows(d, lens):
    """A slot with hist_lens == 0 fetches nothing and leaves the chunk
    pipeline where it was: the next live row finds its first chunk in the
    slot its predecessor started it in."""
    nkv, qpk = (2, 7) if d == 64 else (4, 7)
    ref, out = _window_both(d=d, nkv=nkv, qpk=qpk, maxp=40, lens=lens, m=2)
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, ref, atol=0.03, rtol=0.03)


@pytest.mark.parametrize("page", PAGES[1:])
@pytest.mark.parametrize("lens", [
    [0, 200, 0, 0, 513, 0, 31, 0], [300, 0, 0, 0, 0, 0, 0, 40], [0, 0, 0, 77],
], ids=["between", "hop", "last-only"])
def test_pallas_dead_slots_at_larger_pages(page, lens):
    """The same pipeline at pages of 32, 64 and 128 tokens: dead slots
    before, between and after live rows, rows that end inside a page."""
    ref, out = _window_both(**QWEN_7B, maxp=640 // page, lens=lens, m=2,
                            page=page)
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, ref, atol=0.03, rtol=0.03)


@pytest.mark.parametrize("page", PAGES)
def test_pallas_row_past_2048_tokens_beside_short_rows(page):
    """A table of 4,096 tokens: one row of 2100 tokens (five chunks) beside
    short rows and a dead slot; every row reads its own pages only."""
    ref, out = _window_both(**QWEN_7B, maxp=4096 // page,
                            lens=[40, 2100, 0, 700], m=5, page=page)
    np.testing.assert_allclose(out, ref, atol=0.03, rtol=0.03)


@pytest.mark.parametrize("lo", [[70, 0, 0], [128, 0, 600], [699, 0, 1030]],
                         ids=["inside a page", "on a page's edge, across "
                              "a chunk", "one token of history left"])
@pytest.mark.parametrize("page", [16, 64])
def test_pallas_window_layer_lo_inside_and_across_a_page(page, lo):
    """A window layer's first visible token (SmallThinker's window layers)
    inside a page, on a page's edge and in a later chunk, at 16-token pages
    and at the derived 64: against the gather with the same ``lo``."""
    from dynamo_tpu.engine.attention import paged_window_attention_pallas
    from dynamo_tpu.engine.model import paged_window_attention_xla
    rng = np.random.default_rng(13)
    d, nkv, qpk, M = 128, 4, 7, 4
    hist = [700, 0, 1100]
    b, maxp = len(hist), 1280 // page

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    table = 1 + rng.permutation(b * maxp).reshape(b, maxp).astype(np.int32)
    pool = (2, nkv, b * maxp + 1, page, d)
    args = (arr(b, nkv * qpk, d), arr(*pool), arr(*pool),
            jnp.asarray(1, jnp.int32), jnp.asarray(table),
            jnp.asarray(hist, jnp.int32), arr(nkv, b, M, d),
            arr(nkv, b, M, d), jnp.asarray(2, jnp.int32), arr(b, nkv, d),
            arr(b, nkv, d))
    lo = jnp.asarray(lo, jnp.int32)
    want = paged_window_attention_xla(*args, qpk, lo=lo)
    got = paged_window_attention_pallas(*args, qpk, interpret=True, lo=lo)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=0.03,
                               rtol=0.03)
    full = paged_window_attention_xla(*args, qpk)
    assert np.max(np.abs(np.asarray(full, np.float32)
                         - np.asarray(want, np.float32))) > 0.02


# -- the cells' geometry (head_dim 128): the window's own columns and the
# -- current token beside the kernel's walk, and its fetch cursor ---------------

#: The cells' query geometry: 7 rows a KV head at 4 KV heads and a page of
#: 64 (Qwen2.5-7B, SmallThinker), 16 at 8 and a page of 32 (Command A+).
CELL_SHAPES = [dict(nkv=4, qpk=7, page=64), dict(nkv=8, qpk=16, page=32)]
SHAPE_IDS = ["7x4 page 64", "16x8 page 32"]


def _columns_case(nkv, qpk, page, hist, M, m, lo=None, seed=21, single=False):
    """(reference, kernel) attention [B, Nh, 128] of window step ``m`` of
    ``M`` (``single``: the decode step, no buffer) over rows of ``hist``
    cache-resident tokens."""
    from dynamo_tpu.engine.attention import paged_window_attention_pallas
    from dynamo_tpu.engine.model import paged_window_attention_xla
    rng = np.random.default_rng(seed)
    d, b = 128, len(hist)
    maxp = max(2, -(-max(hist) // page))
    table = 1 + rng.permutation(b * maxp).reshape(b, maxp).astype(np.int32)

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    pool = (2, nkv, b * maxp + 1, page, d)
    head = (arr(b, nkv * qpk, d), arr(*pool), arr(*pool),
            jnp.asarray(1, jnp.int32), jnp.asarray(table),
            jnp.asarray(hist, jnp.int32))
    own = (arr(b, nkv, d), arr(b, nkv, d))
    kw = {} if lo is None else {"lo": jnp.asarray(lo, jnp.int32)}
    if single:
        want = paged_decode_attention_xla(*head, *own, qpk, **kw)
        got = paged_decode_attention_pallas(*head, *own, qpk, interpret=True,
                                            **kw)
    else:
        args = (*head, arr(nkv, b, M, d), arr(nkv, b, M, d),
                jnp.asarray(m, jnp.int32), *own)
        want = paged_window_attention_xla(*args, qpk, **kw)
        got = paged_window_attention_pallas(*args, qpk, interpret=True, **kw)
    assert got.dtype == jnp.bfloat16 and got.shape == (b, nkv * qpk, d)
    return np.asarray(want, np.float32), np.asarray(got, np.float32)


@pytest.mark.parametrize("shape", CELL_SHAPES, ids=SHAPE_IDS)
@pytest.mark.parametrize("M, m", [(4, 0), (4, 1), (4, 3), (8, 0), (8, 1),
                                  (8, 7)])
def test_extra_columns_at_the_first_the_second_and_the_last_step(shape, M, m):
    """No column of the buffer written yet, one, and all but the last: the
    current token stands in column m of the tile and nothing after it is
    seen, whatever the buffer holds there."""
    want, got = _columns_case(**shape, hist=[70, 700, 0, 33], M=M, m=m)
    np.testing.assert_allclose(got, want, atol=0.03, rtol=0.03)


@pytest.mark.parametrize("shape", CELL_SHAPES, ids=SHAPE_IDS)
@pytest.mark.parametrize("m", [0, 2])
def test_extra_columns_of_rows_with_no_history_at_all(shape, m):
    """Fresh rows and dead slots only: no page is fetched, the attention is
    the softmax over the window's columns and the current token (at step 0
    the current token's V itself)."""
    want, got = _columns_case(**shape, hist=[0, 0, 0], M=4, m=m)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=0.03, rtol=0.03)


@pytest.mark.parametrize("shape", CELL_SHAPES, ids=SHAPE_IDS)
@pytest.mark.parametrize("M, m", [(4, 3), (8, 5)])
def test_extra_columns_of_a_dead_slot_between_live_rows(shape, M, m):
    """A slot without history between rows with some: it emits the neutral
    triple while the pipeline holds the next live rows' first chunks."""
    want, got = _columns_case(**shape, hist=[130, 0, 0, 600, 0, 64], M=M,
                               m=m)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=0.03, rtol=0.03)


@pytest.mark.parametrize("shape", CELL_SHAPES, ids=SHAPE_IDS)
@pytest.mark.parametrize("lo", [[301, 0, 0, 45], [303, 0, 2, 41],
                                [300, 0, 0, 39]],
                         ids=["one column hidden", "all but the newest hidden",
                              "the window starts at the first column"])
def test_extra_columns_window_layer_lo_inside_the_extra_columns(shape, lo):
    """A window layer whose first visible position lies past the history
    (lo > hist_lens): no page is read for that row and the columns of the
    buffer before lo are masked too; column j stands at hist_lens + j.
    Beside it a row that sees everything and a dead slot."""
    hist = [300, 500, 0, 40]
    want, got = _columns_case(**shape, hist=hist, M=8, m=4, lo=lo)
    np.testing.assert_allclose(got, want, atol=0.03, rtol=0.03)
    full, _ = _columns_case(**shape, hist=hist, M=8, m=4)
    assert np.max(np.abs(full[0] - want[0])) > 0.02   # the mask bites
    np.testing.assert_allclose(full[1], want[1], atol=1e-6)


@pytest.mark.parametrize("shape", CELL_SHAPES, ids=SHAPE_IDS)
@pytest.mark.parametrize("lo", [None, [0, 190, 0, 64]],
                         ids=["full layers", "a window layer"])
def test_extra_columns_of_the_single_step(shape, lo):
    """The decode step outside a window: the current token is the only
    column out of the pool (the same kernel, no buffer operand)."""
    want, got = _columns_case(**shape, hist=[5, 200, 0, 64], M=0, m=0,
                               lo=lo, single=True)
    np.testing.assert_allclose(got, want, atol=0.03, rtol=0.03)


@pytest.mark.parametrize("lens", [
    [1500, 0, 513, 512, 0, 1, 1100],   # five turns, then a row of three
    [40, 30, 20, 10, 5, 600, 1],       # one turn a row: the cursor hops rows
    [2100],                            # one row alone, more turns than slots
], ids=["long and short", "a turn a row", "alone"])
@pytest.mark.parametrize("shape", CELL_SHAPES, ids=SHAPE_IDS)
def test_fetch_cursor_runs_ahead_across_rows(shape, lens):
    """The fetch cursor walks the live rows' chunks SLOTS turns ahead of
    the multiplication: rows with more turns than buffers, rows of one turn
    (the cursor is two rows on), dead slots between them."""
    want, got = _columns_case(**shape, hist=lens, M=4, m=2)
    np.testing.assert_allclose(got, want, atol=0.03, rtol=0.03)


def test_extra_columns_probabilities_stay_float32():
    """Against a float32 softmax over bfloat16 values: rows whose weight
    lies in the window's columns (no history) come out to the rounding of
    the OUTPUT alone, which probabilities rounded to bfloat16 would not."""
    nkv, qpk, M, m = 4, 7, 8, 7
    want, got = _columns_case(nkv, qpk, 64, hist=[0, 0], M=M, m=m, seed=5)
    rng = np.random.default_rng(5)          # the case's own draws, again
    b, d = 2, 128
    rng.permutation(b * 2)

    def arr(*shape):
        return np.asarray(jnp.asarray(rng.standard_normal(shape),
                                      jnp.bfloat16), np.float32)

    q = arr(b, nkv * qpk, d).reshape(b, nkv, qpk, d)
    arr(2, nkv, b * 2 + 1, 64, d), arr(2, nkv, b * 2 + 1, 64, d)
    k_own, v_own = arr(b, nkv, d), arr(b, nkv, d)
    k_win, v_win = arr(nkv, b, M, d), arr(nkv, b, M, d)
    k = np.concatenate([k_win.transpose(1, 0, 2, 3)[:, :, :m],
                        k_own[:, :, None]], axis=2)
    v = np.concatenate([v_win.transpose(1, 0, 2, 3)[:, :, :m],
                        v_own[:, :, None]], axis=2)
    s = np.einsum("bngd,bnjd->bngj", q, k) / np.sqrt(d)
    p = np.exp(s - s.max(-1, keepdims=True))
    exact = np.einsum("bngj,bnjd->bngd", p / p.sum(-1, keepdims=True), v)
    exact = exact.reshape(b, nkv * qpk, d)
    # bfloat16 keeps 8 bits: the output's rounding is under 2**-8 of a value.
    np.testing.assert_allclose(got, exact, rtol=2 ** -8, atol=2 ** -9)


@pytest.mark.parametrize("shape, want", [
    ((16, 4, 128, 2), 32),    # qwen2.5-7b bf16: 512 tokens a chunk
    ((16, 8, 128, 2), 16),    # llama-3-8b
    ((16, 2, 64, 2), 64),     # qwen2.5-0.5b: small pages, the cap
    ((16, 4, 128, 1), 64),    # int8 pages hold twice the tokens
    ((32, 4, 128, 2), 16),    # the same 512 tokens at a page of 32,
    ((64, 4, 128, 2), 8),     # ... of 64 (what "auto" derives at 4 x 128)
    ((128, 4, 128, 2), 4),    # ... and of 128
    ((32, 8, 128, 2), 8),     # llama-3-8b's derived page: 256 tokens
    ((128, 8, 128, 2), 2),    # large pages: what CHUNK_BYTES holds
    ((128, 32, 128, 2), 1),   # the floor: one lane tile of tokens
])
def test_pages_per_chunk_follows_page_bytes(shape, want):
    from dynamo_tpu.engine.attention import pages_per_chunk
    assert pages_per_chunk(*shape) == want


def test_pallas_rejects_unpackable_head_dim():
    with pytest.raises(AssertionError):
        _both(_case(48, b=2, nkv=1, qpk=2, maxp=4, seq_lens=[8, 8]), qpk=2)


# -- the reader of a latent pool ------------------------------------------------

#: The DeepSeek-V3.2 cell's widths (128 heads, entries of 640 lanes of which
#: 512 are the value, index keys of 128 scored by 64 heads, 2,048 keys kept,
#: a page of 64), cut in batch and table only: four rows, a table of 512
#: tokens (under index_topk: every key in context is attended) or of 2,560
#: (over it: the indexer chooses).
LATENT_PAGE, LATENT_WINDOW = 64, 8
LATENT_CASES = [
    (table, form, m, weights)
    for table in (8, 40) for weights in ("bf16", "int8")
    for form, m in (("window", 0), ("window", LATENT_WINDOW - 1),
                    ("decode_forward", 0))]


@pytest.mark.parametrize("table, form, m, weights", LATENT_CASES)
def test_latent_reader_matches_the_xla_walk(table, form, m, weights):
    """attention.latent_history_pallas and latent_index_pallas (interpreted)
    as model.latent_window_attention's ``kernels`` against XLA's walk
    (None), as the window step calls it (eight window columns of which
    ``m`` are written) and as decode_forward does (no window columns): rows
    without history, with one token, at a page's edge and at the bucket's
    end in one batch, the table and the mask padded to the widest table the
    caller has; the attention and the counts [attended, in context]. The
    indexer's kernel scores the pool's index keys where the table can hold
    more than index_topk keys; under it no indexer runs."""
    import jax

    from dynamo_tpu.engine import model
    from dynamo_tpu.engine.attention import (latent_history_pallas,
                                             latent_index_pallas)
    from dynamo_tpu.engine.backends import XLA, Backends
    from dynamo_tpu.engine.config import DeepseekV32Spec
    from dynamo_tpu.engine.quant import quantize_weight
    spec = DeepseekV32Spec(
        name="latent", vocab_size=64, hidden_size=64, intermediate_size=64,
        num_layers=2, num_heads=128, num_kv_heads=128, head_dim=192,
        num_experts=4, num_experts_per_tok=2, moe_intermediate_size=64,
        num_routed_experts=4, num_shared_experts=1,
        rope_yarn=(40.0, 4096, 32.0, 1.0, 1.0))
    assert spec.kv_entry == (1, (640, 128)) and spec.index_topk == 2048
    page, M = LATENT_PAGE, LATENT_WINDOW if form == "window" else 0
    selecting = table * page + M + 1 > spec.index_topk
    assert selecting == (table == 40)
    hist_lens = [0, 1, 3 * page, table * page]
    b, nh, L = len(hist_lens), spec.num_heads, 2
    rng = np.random.default_rng(table + m)
    pages = b * table + 2

    def normal(*shape, scale=1.0, dtype=jnp.bfloat16):
        return jnp.asarray(rng.standard_normal(shape) * scale, dtype)

    e_cache = normal(L, 1, pages, page, 640, scale=0.3)
    e_cache = e_cache.at[..., 576:].set(0)      # an entry's padding lanes
    i_cache = normal(L, 1, pages, page, 128)
    pt = np.stack([rng.permutation(np.arange(1, pages - 1))[:table]
                   for _ in range(b)]).astype(np.int32)
    matrices = [rng.standard_normal((512, nh * 128)).astype(np.float32) * 0.05
                for _ in range(2)]
    if weights == "int8":
        wk_b, wv_b = (jax.tree.map(jnp.asarray, quantize_weight(w))
                      for w in matrices)
    else:
        wk_b, wv_b = (jnp.asarray(w, jnp.bfloat16) for w in matrices)
    q = model.LatentQuery(
        nope=normal(b, nh, 128), rope=normal(b, nh, 64),
        iq=normal(b, 64, 128), iw=normal(b, 64, dtype=jnp.float32),
        wk_b=wk_b, wv_b=wv_b)
    args = (q, e_cache, i_cache, jnp.asarray(1, jnp.int32), jnp.asarray(pt),
            jnp.asarray(hist_lens, jnp.int32),
            normal(1, b, M, 640, scale=0.3), normal(1, b, M, 128),
            jnp.asarray(m, jnp.int32), normal(b, 1, 640, scale=0.3),
            normal(b, 1, 128))
    live = jnp.asarray([False, True, True, True])

    def run(record):
        return jax.jit(lambda *a: model.latent_window_attention(
            *a, spec, live, backends=record))(*args)

    want, want_counts = run(XLA)
    # As the runner's record binds them: one kernel for every table up to
    # 48 pages.
    record = Backends(attention="pallas", interpret=True, table=48)
    reader, indexer = record.latent_readers()
    assert (reader.func, indexer.func) == (latent_history_pallas,
                                           latent_index_pallas)
    got, got_counts = run(record)
    np.testing.assert_array_equal(np.asarray(got_counts),
                                  np.asarray(want_counts))
    context = sum(hist_lens[1:]) + 3 * (m + 1)
    assert float(want_counts[1]) == context
    assert (float(want_counts[0]) < context) == selecting
    want, got = (np.asarray(x, np.float32) for x in (want, got))
    assert np.abs(want).max() > 0.05
    np.testing.assert_allclose(got, want, atol=0.01, rtol=0.03)


# -- the indexer of a latent pool -----------------------------------------------

#: Rows of one batch, tokens of cache-resident history, at the cell's widths
#: (64 index heads of 128, a page of 64, 2,048 keys kept, eight window
#: columns), a page table of 40 pages: a kernel turn is 2,048 tokens, scored
#: 512 at a time.
INDEX_ROWS = {
    "a dead row, one token, inside a page, the table's limit":
        [0, 1, 3 * LATENT_PAGE + 5, 40 * LATENT_PAGE],
    "shorter than a chunk, a chunk and a token, a page's edge, over k by one":
        [1500, 2049, 33 * LATENT_PAGE, 2040],
}


def _index_case(lens, seed, ties=False):
    """(iq, iw, i_cache, layer, page table, lengths) and XLA's scores of the
    gathered bucket. ``ties``: every key of the longest row is ONE key."""
    from dynamo_tpu.engine import model
    from dynamo_tpu.engine.kv_quant import gather_pages_folded
    page, table = LATENT_PAGE, 40
    b = len(lens)
    rng = np.random.default_rng(seed)
    pages = b * table + 2
    i_cache = jnp.asarray(rng.standard_normal((2, 1, pages, page, 128)),
                          jnp.bfloat16)
    pt = np.stack([rng.permutation(np.arange(1, pages - 1))[:table]
                   for _ in range(b)]).astype(np.int32)
    if ties:
        i_cache = i_cache.at[:, :, pt[int(np.argmax(lens))]].set(
            i_cache[:, :, :1, :1, :])
    iq = jnp.asarray(rng.standard_normal((b, 64, 128)), jnp.bfloat16)
    iw = jnp.asarray(rng.standard_normal((b, 64)), jnp.float32)
    layer = jnp.asarray(1, jnp.int32)
    args = (iq, iw, i_cache, layer, jnp.asarray(pt),
            jnp.asarray(lens, jnp.int32))
    want = np.asarray(model.index_scores(
        iq[:, None], iw[:, None],
        gather_pages_folded(i_cache, layer, jnp.asarray(pt))[0])[:, 0])
    return args, want


@pytest.mark.parametrize("table", [None, 48, 128],
                         ids=["its own table", "bound to 48 pages",
                              "bound to the launcher's limit"])
@pytest.mark.parametrize("lens", list(INDEX_ROWS.values()),
                         ids=list(INDEX_ROWS))
def test_latent_indexer_scores_what_xla_scores(lens, table):
    """attention.latent_index_pallas (interpreted) against model.index_scores
    over the gathered bucket: the same float32 score at every token a row
    holds (bfloat16 products, float32 sums: only the order of the 64 heads'
    sum differs), whatever table the kernel is bound to (a runner binds its
    widest, so every bucket's program shares one trace); what lies past a
    row's length is undefined and select_topk never reads it."""
    import jax

    from dynamo_tpu.engine.attention import latent_index_pallas
    args, want = _index_case(lens, seed=len(lens) + (table or 0))
    got = np.asarray(jax.jit(lambda *a: latent_index_pallas(
        *a, interpret=True, table=table))(*args))
    assert got.shape == want.shape == (len(lens), 40 * LATENT_PAGE)
    assert got.dtype == np.float32
    for r, n in enumerate(lens):
        np.testing.assert_allclose(got[r, :n], want[r, :n], rtol=2e-5,
                                   atol=2e-5 * np.abs(want[r]).max())
    assert np.abs(want).max() > 10


@pytest.mark.parametrize("m", [0, LATENT_WINDOW - 1])
@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
@pytest.mark.parametrize("lens", list(INDEX_ROWS.values()),
                         ids=list(INDEX_ROWS))
def test_the_kernel_s_scores_serve_select_topk_s_set(lens, ties, m):
    """model.select_topk over the kernel's scores and the nine keys that are
    not in the pool yet (as model.latent_window_attention joins them)
    against the same over XLA's scores: the sets are equal wherever the
    margin exceeds the gap (a key may swap sides only within 1e-5 of the
    k-th score's scale), exactly k keys a row, every valid key where there
    are fewer, nothing past a row's length; with ``ties`` every key of the
    longest row is ONE key, every score ties at the k-th and all are
    kept."""
    import jax

    from dynamo_tpu.engine import model
    from dynamo_tpu.engine.attention import latent_index_pallas
    M, k = LATENT_WINDOW, 2048
    b, hist = len(lens), 40 * LATENT_PAGE
    args, old = _index_case(lens, seed=len(lens) + m, ties=ties)
    iq, iw = args[:2]
    rng = np.random.default_rng(m)
    new = np.concatenate(
        [np.asarray(model.index_scores(
            iq[:, None], iw[:, None],
            jnp.asarray(rng.standard_normal((b, n, 128)), jnp.bfloat16))[:, 0])
         for n in (M, 1)], axis=-1)
    valid = np.concatenate(
        [np.arange(hist)[None] < np.asarray(lens)[:, None],
         np.broadcast_to(np.concatenate([np.arange(M) < m, [True]]),
                         (b, M + 1))], axis=-1)
    got_old = np.asarray(jax.jit(lambda *a: latent_index_pallas(
        *a, interpret=True, table=48))(*args))
    # What a row does not hold is undefined: poison it.
    got_old = np.where(valid[:, :hist], got_old, np.nan).astype(np.float32)

    def choice(old):
        return np.asarray(model.select_topk(
            jnp.asarray(np.concatenate([old, new], axis=-1)),
            jnp.asarray(valid), k))

    want, got = choice(old), choice(got_old)
    assert (got & ~valid).sum() == 0
    longest = int(np.argmax(lens))
    if ties:
        assert got[longest, :lens[longest]].all()
        assert got[longest].sum() >= lens[longest] > k
    else:
        assert (got.sum(-1) == np.minimum(valid.sum(-1), k)).all()
    scores = np.concatenate([old, new], axis=-1)
    for r in range(b):
        differ = np.flatnonzero(got[r] != want[r])
        if differ.size:     # only AT the boundary
            kth = np.sort(scores[r][valid[r]])[-k]
            assert np.abs(scores[r][differ] - kth).max() \
                < 1e-5 * np.abs(scores[r][valid[r]]).max()
