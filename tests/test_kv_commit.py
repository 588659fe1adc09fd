"""The decode window's commit in place == kv_quant.scatter_tokens.

attention.commit_window_pallas rewrites, of each live row, only the pages
its window touched, where they lie in the pool; the scatter it stands in
for writes the same bf16 values to the same (page, offset) and sends what
does not land to scratch page 0. Interpret mode on the CPU checks results,
bit for bit on every page but page 0; tests/test_tpu_compile.py compiles
the kernel for a described v5e and reads the window program's text for
pool-sized operations. Which program takes which commit is the runner's
observation (backends.choose), checked here too.
"""

import dataclasses
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.attention import commit_window_pallas, window_pages
from dynamo_tpu.engine.backends import choose
from dynamo_tpu.engine.config import (PRESETS, EngineConfig, ModelSpec,
                                      pool_access)
from dynamo_tpu.engine.kv_quant import scatter_tokens, window_token_slots
from dynamo_tpu.engine.runner import (PK_CAP, PK_OVERRIDE, PK_POS, PK_PREFIX,
                                      PK_SEQLEN, PK_TOKEN, ModelRunner)

PAGE = 16
D = 128
#: (layers, kv heads) of the two benchmark cells' pools.
CELLS = {"qwen2.5-7b": (28, 4), "smallthinker-21b-a3b": (24, 4)}


def scatter_commit(k_cache, kbuf, positions0, cap, seq_lens0, page_table):
    """What ModelRunner's window program does where it scatters."""
    dest, off = window_token_slots(positions0, cap, seq_lens0, page_table,
                                   kbuf.shape[3], k_cache.shape[3])
    return scatter_tokens(k_cache, kbuf.transpose(0, 1, 3, 2, 4), dest, off)


#: position, pages held (cap = pages x 16; 0: a dead slot) of each row:
#: dead slots between live rows, a window that starts at offset 15, a row
#: at its cap, one a token under it, one that fills its last page exactly.
ROWS = [(15, 4), (0, 0), (37, 4), (0, 0), (64, 4), (63, 4), (50, 4), (0, 0),
        (3, 2)]


#: The same at a page of several 16-row tiles (position, pages held): a
#: window that crosses a page, one that crosses a tile inside a page, one
#: that starts on a page's last row, a row at its cap, one a token under it.
def rows_at(page):
    return [(page - 3, 4), (0, 0), (page + 13, 4), (0, 0), (4 * page, 4),
            (4 * page - 1, 4), (2 * page + 16, 4), (0, 0), (3, 2),
            (page - 1, 3)]


def _case(layers, nkv, window, seed=0, page=PAGE, rows=ROWS, widths=(D, D)):
    rng = np.random.default_rng(seed)
    b, maxp = len(rows), 4
    pages = 1 + b * maxp
    pos = np.array([p for p, _ in rows], np.int32)
    held = np.array([n for _, n in rows], np.int32)
    table = (1 + rng.permutation(pages - 1)).reshape(b, maxp).astype(np.int32)
    table[held == 0] = 0
    pool = (layers, nkv, pages, page)
    buf = (layers, nkv, b, window)
    args = [jnp.asarray(rng.standard_normal(s), jnp.bfloat16)
            for s in ((*pool, widths[0]), (*pool, widths[1]),
                      (*buf, widths[0]), (*buf, widths[1]))]
    return (*args, jnp.asarray(pos), jnp.asarray(held * page),
            jnp.asarray(np.where(held > 0, pos + 1, 0).astype(np.int32)),
            jnp.asarray(table))


@pytest.mark.parametrize("cell", list(CELLS))
@pytest.mark.parametrize("window", [1, 4, 8, 20],
                         ids=["window 1", "window 4", "window 8",
                              "window 20 (three pages)"])
def test_in_place_commit_equals_the_scatter_bit_for_bit(window, cell):
    _commit_equals_scatter(_case(*CELLS[cell], window))


@pytest.mark.parametrize("page", [32, 64, 128])
@pytest.mark.parametrize("window", [4, 8, 20],
                         ids=["window 4", "window 8",
                              "window 20 (three tiles)"])
def test_in_place_commit_at_larger_pages_equals_the_scatter(window, page):
    """A page of several tiles: the commit moves the 16-row tiles the
    tokens fall on, and the pool comes out as the scatter leaves it."""
    _commit_equals_scatter(_case(3, 2, window, page=page,
                                 rows=rows_at(page)))


@pytest.mark.parametrize("page", [16, 64])
@pytest.mark.parametrize("window", [4, 8])
def test_in_place_commit_of_a_latent_pool_s_two_widths(window, page):
    """A latent pool (ModelSpec.kv_entry): one "head", latent entries of
    640 lanes in the first array and index keys of 128 in the second, one
    page table: both come out as the scatter leaves them."""
    _commit_equals_scatter(_case(
        9, 1, window, page=page, widths=(640, 128),
        rows=ROWS if page == PAGE else rows_at(page)))


def _commit_equals_scatter(case):
    kc, vc, kb, vb, pos, cap, seq, table = case
    k_new, v_new = commit_window_pallas(kc, vc, kb, vb, pos, cap, seq, table,
                                        interpret=True)
    k_ref = scatter_commit(kc, kb, pos, cap, seq, table)
    v_ref = scatter_commit(vc, vb, pos, cap, seq, table)
    for new, ref, old in ((k_new, k_ref, kc), (v_new, v_ref, vc)):
        np.testing.assert_array_equal(
            np.asarray(new[:, :, 1:]).view(np.uint16),
            np.asarray(ref[:, :, 1:]).view(np.uint16))
        # What does not land is written nowhere: page 0 as it was.
        np.testing.assert_array_equal(
            np.asarray(new[:, :, 0]).view(np.uint16),
            np.asarray(old[:, :, 0]).view(np.uint16))
    changed = np.flatnonzero(
        (np.asarray(k_new) != np.asarray(kc)).any(axis=(0, 1, 3, 4)))
    assert len(changed) and set(changed) <= set(np.asarray(table).ravel())


@pytest.mark.parametrize("page, tile", [(16, None), (64, 16), (128, 16),
                                        (64, None)])
def test_window_pages_names_every_landing_token_once(page, tile):
    """The kernel's schedule against the scatter's index arrays: each
    (row, tile) entry's run [r0, r0 + n) of tokens [m0, m0 + n), at the
    tile's first row t0 of its page, is exactly the live tokens the
    scatter sends to that page."""
    window = 20
    rows = ROWS if page == PAGE else rows_at(page)
    *_, pos, cap, seq, table = _case(1, 1, window, page=page, rows=rows)
    sched = [np.asarray(a) for a in window_pages(
        pos, cap, seq, table, window, page, tile)]
    pid, r0, m0, n = sched[:4]
    # A page of one tile has no t0: the program the parent lowered.
    assert len(sched) == (4 if (tile or page) == page else 5)
    t0 = sched[4] if len(sched) == 5 else np.zeros_like(pid)
    j = -(-(window - 1) // (tile or page)) + 1
    assert pid.shape == (len(rows) * j,)
    assert (t0 % (tile or page) == 0).all()
    landed = {}
    for i in np.flatnonzero(n):
        for t in range(n[i]):
            landed[(i // j, m0[i] + t)] = (pid[i], t0[i] + r0[i] + t)
    want = {}
    for b, (p, held) in enumerate(rows):
        for m in range(window):
            if held and p + m < held * page:
                want[(b, m)] = (int(table[b, (p + m) // page]),
                                (p + m) % page)
    assert landed == want
    assert (pid[n == 0] == 0).all()


# -- the whole window program -------------------------------------------------

SPEC128 = ModelSpec(name="tiny-128", vocab_size=256, hidden_size=256,
                    intermediate_size=256, num_layers=2, num_heads=2,
                    num_kv_heads=1, max_position_embeddings=2048)


def _runner(**kw) -> ModelRunner:
    defaults = dict(model=SPEC128, page_size=PAGE, num_pages=40,
                    max_pages_per_seq=8, max_num_seqs=4,
                    prefill_buckets=(32,), max_prefill_tokens=32)
    defaults.update(kw)
    return ModelRunner(EngineConfig(**defaults), seed=3)


def _window_of(runner, window):
    """One decode window over a pool of noise: rows at a page's last token
    (the window crosses a page edge), dead, inside a page (at a page of 64
    its window crosses a 16-row tile), and one token under its cap."""
    page = runner.config.page_size
    rng = np.random.default_rng(5)
    noise = jnp.asarray(rng.standard_normal(runner.k_cache.shape),
                        jnp.bfloat16)
    runner.k_cache = jax.device_put(noise, runner.kv_sharding)
    runner.v_cache = jax.device_put(-noise, runner.kv_sharding)
    packed = np.zeros((4, PK_PREFIX + 8), np.int32)
    for slot, (pos, pages) in enumerate([(page - 1, 2), (0, 0),
                                         (2 * page + 8, 4),
                                         (3 * page - 1, 3)]):
        if not pages:
            continue
        packed[slot, [PK_OVERRIDE, PK_TOKEN, PK_POS, PK_SEQLEN, PK_CAP]] = (
            1, 7 + slot, pos, pos + 1, pages * page)
        packed[slot, PK_PREFIX:PK_PREFIX + pages] = 1 + 8 * slot + np.arange(
            pages)
    toks, *_ = runner.decode_window(packed, window)
    return (np.asarray(toks), np.asarray(runner.k_cache).view(np.uint16),
            np.asarray(runner.v_cache).view(np.uint16))


@pytest.mark.parametrize("page", [PAGE, 64])
@pytest.mark.parametrize("window", [4, 8])
def test_run_window_in_place_gives_the_scatter_s_tokens_and_pool(window,
                                                                  page):
    in_place = _runner(attention_backend="pallas", page_size=page)
    assert in_place.backends.kv_commit == "in_place"
    scatter = _runner(attention_backend="pallas", page_size=page)
    # Steer the twin: same reader.
    scatter.backends = dataclasses.replace(scatter.backends,
                                           kv_commit="scatter")
    toks_a, k_a, v_a = _window_of(in_place, window)
    toks_b, k_b, v_b = _window_of(scatter, window)
    np.testing.assert_array_equal(toks_a, toks_b)
    np.testing.assert_array_equal(k_a[:, :, 1:], k_b[:, :, 1:])
    np.testing.assert_array_equal(v_a[:, :, 1:], v_b[:, :, 1:])
    text = in_place._get_window(window, 8).lower(
        *_window_args(in_place)).as_text()
    assert "stablehlo.scatter" not in text


def _window_args(runner):
    return (runner.params, runner.k_cache, runner.v_cache, runner.tokens_dev,
            jnp.zeros((runner.config.max_num_seqs, PK_PREFIX + 8), jnp.int32),
            runner._rng)


# -- which program takes which commit ----------------------------------------

def _picked(attention_backend, mesh_size, head_dim, quant_kv):
    config = SimpleNamespace(attention_backend=attention_backend,
                             page_size=PAGE, max_pages_per_seq=8,
                             spec_decode=None)
    spec = SimpleNamespace(head_dim=head_dim, latent=False, recurrent=False,
                           compressed_keys=False,
                           index_topk=0, num_experts=0, num_heads=28,
                           num_kv_heads=4)
    return choose(config, spec, "tpu", mesh_size, quant_kv).kv_commit


@pytest.mark.parametrize("attention_backend, mesh_size, head_dim, quant_kv, "
                         "want", [
    ("pallas", 1, 128, None, "in_place"),   # both benchmark cells
    ("xla", 1, 128, None, "scatter"),       # the CPU under "auto"; a request
    ("xla", 4, 128, None, "scatter"),       # a mesh (its reader is XLA's)
    ("pallas", 4, 128, None, "scatter"),    # never built; the mesh alone says
    ("pallas", 1, 64, None, "scatter"),     # a packed head
    ("pallas", 1, 128, "int8", "scatter"),  # QuantKV: two arrays, 32-row tiles
])
def test_commit_is_decided_from_reader_mesh_head_and_pool(
        attention_backend, mesh_size, head_dim, quant_kv, want):
    if attention_backend == "pallas" and mesh_size > 1:
        # Refused before a writer is asked for; the inner rule still says.
        with pytest.raises(ValueError, match="runs on one device"):
            _picked(attention_backend, mesh_size, head_dim, quant_kv)
        assert pool_access(attention_backend, "tpu", mesh_size, head_dim,
                           quant_kv)[1] == want
        return
    assert _picked(attention_backend, mesh_size, head_dim, quant_kv) == want


@pytest.mark.parametrize("kw", [
    dict(tp=2), dict(model=PRESETS["tiny-test"], attention_backend="pallas"),
    dict(attention_backend="pallas", quant_kv="int8"), dict()],
    ids=["a mesh", "head_dim 32 (packed)", "QuantKV", "the CPU under auto"])
def test_programs_off_the_predicate_keep_the_scatter(kw):
    """Built for real: the runner's label says scatter, the program's
    lowered text holds the scatter of both pools and no commit kernel."""
    runner = _runner(**kw)
    assert runner.backends.kv_commit == "scatter"
    with runner.mesh:
        text = runner._get_window(4, 8).lower(*_window_args(runner)).as_text()
    pools = 4 if kw.get("quant_kv") else 2  # values and scales
    assert text.count("stablehlo.scatter") >= pools
    assert "_commit_kernel" not in text
