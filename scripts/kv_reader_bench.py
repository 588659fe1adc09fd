#!/usr/bin/env python3
"""The K-and-V reader alone on the device this process holds: the chunk
turn of ``attention._decode_kernel`` by geometry and by form, the table at
``attention.reader_turn``.

    chiprun -- python3 scripts/kv_reader_bench.py
        [--geometries 16x1,4x1,8x1,32x1,16x2,4x7] [--tokens 200,400,650,1000]
        [--turns rows,heads,...] [--layers 48] [--page 16]

A geometry is KV heads x query rows a head, head_dim 128, bfloat16 pages of
``--page`` tokens; ``--tokens`` the history of each row (four rows of 200 to
1,245 tokens are the sixth cell's, ``ouro-2.6b.reasoning-1k``). A call is
``attention._hist_flash_pallas`` of one layer; ``--layers`` calls run in a
scan whose carried queries take a (vanishing) share of every call's output:
XLA hoists what a scan does not depend on (PERF.md section 6). One JSON line
a (geometry, turn) into ``chiprun_out/kv_reader.jsonl`` and stdout, with the
milliseconds a call (the least of three loops' mean, each ending in
``block_until_ready``), the microseconds a chunk turn that is, and the
largest difference of the window attention from the gather's
(``model.paged_window_attention_xla``).

Turns: ``rows`` and ``heads`` are the kernel's own (``reader_turn`` is
overridden so that either runs at any geometry). The others are the parts
and the form that was dropped, kept HERE so that the table can be read
again:

- ``<turn>+noscores`` / ``+novalues`` / ``+nofetch`` (any of them, ``+``
  between): the heads turn with its score product or its value products
  replaced by a load of one tile, and any turn with no page copied or
  awaited (the products run over what the buffers hold): what is left is
  the other parts (their outputs are wrong on purpose: no difference is
  reported). The copies fly UNDER the products, so a part's time is read
  with ``+nofetch`` and the walk's with both products out;
- ``lanes-vpu``: the statistics on the [tokens, heads] tile as the product
  leaves it (heads on lanes, the reduction over sublanes) and the values as
  a multiply-accumulate on the VPU, ``acc[h] += p[t, h] * V_h[t, :]``, no
  MXU (ISSUE 49's form V-b);
- ``lanes-mxu``: the same statistics, the probabilities transposed once a
  turn and the values as the heads turn takes them (ISSUE 49's form V-a as
  written: sixteen registers of ``exp`` a turn where the heads turn's
  transposed scores take two).

On the CPU the kernels are interpreted: a tiny ``--layers`` and ``--tokens``
say that the script runs, never a time."""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402
from jax.experimental import pallas as pl                     # noqa: E402
from jax.experimental.pallas import tpu as pltpu              # noqa: E402

from dynamo_tpu.engine import attention, model                # noqa: E402
from dynamo_tpu.engine.attention import NEG_INF, SLOTS        # noqa: E402

OUT = os.path.join("chiprun_out", "kv_reader.jsonl")
WINDOW = 4      # columns of the window buffer in the comparison


# -- the form that was dropped: statistics with the heads on lanes ------------

def _lanes_kernel(layer_ref, page_table_ref, seq_lens_ref, q_ref, k_hbm,
                  v_hbm, acc_ref, m_ref, l_ref, k_buf, v_buf, sems, g_ref,
                  cur_ref, *, page_size: int, vpu: bool):
    """_decode_kernel's walk at one query row a head with the flash
    statistics on the [tokens, heads] tile: m and l are lane vectors. The
    values on the VPU (``vpu``) or, the probabilities transposed, as
    attention._heads_values takes them."""
    _, nkv, ppc, _, _ = k_buf.shape
    b, nb = pl.program_id(0), pl.num_programs(0)
    seq_len = seq_lens_ref[b]
    tokens = ppc * page_size
    num_chunks = pl.cdiv(seq_len, tokens)
    scale = 128 ** -0.5

    @pl.when(b == 0)
    def _():
        g_ref[0] = 0
        for slot in range(SLOTS):
            for h in range(nkv):
                k_buf[slot, h] = jnp.zeros(k_buf.shape[2:], k_buf.dtype)
                v_buf[slot, h] = jnp.zeros(v_buf.shape[2:], v_buf.dtype)

    issue_fetch, wait_fetch, prime = attention._fetch_pipeline(
        page_table_ref, seq_lens_ref, cur_ref,
        ((k_hbm, k_buf), (v_hbm, v_buf)), sems, layer_ref[0], nb, page_size,
        lambda r: 0)
    pl.when(b == 0)(prime)
    q_all = jnp.concatenate(
        [q_ref[0], jnp.zeros((128 - nkv, 128), q_ref.dtype)], axis=0)
    token = jax.lax.broadcasted_iota(jnp.int32, (tokens, 128), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (tokens, 128), 1)

    def body(c, carry, g0):
        m, l, acc = carry
        slot = jax.lax.rem(g0 + c, SLOTS)
        wait_fetch(b, c, slot)
        r = jax.lax.dot_general(
            k_buf[slot].reshape(nkv * tokens, 128), q_all,
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        s = r[:tokens]
        for h in range(1, nkv):
            s = jnp.where(lane == h, r[h * tokens:(h + 1) * tokens], s)
        s = jnp.where(c * tokens + token < seq_len, s * scale, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)                       # [1, 128 heads]
        l_new = l * alpha + jnp.sum(p, axis=0, keepdims=True)
        if vpu:
            new = []
            for h in range(nkv):
                v = v_buf[slot, h].reshape(tokens, 128).astype(jnp.float32)
                new.append(acc[h] * alpha[:, h:h + 1] + jnp.sum(
                    p[:, h:h + 1] * v, axis=0, keepdims=True))
            acc_new = tuple(new)
        else:
            # [128 heads, tokens] and alpha down the sublanes, both through
            # the transpose unit.
            alpha_t = jnp.broadcast_to(alpha, (128, 128)).T[:nkv]
            acc_new = acc * alpha_t + attention._heads_values(
                p.T[:nkv], lambda h: v_buf[slot, h].reshape(tokens, 128),
                nkv, 1)
        issue_fetch()
        return m_new, l_new, acc_new

    g0 = g_ref[0]
    zero = jnp.zeros((1, 128), jnp.float32)
    m, l, acc = jax.lax.fori_loop(
        0, num_chunks, functools.partial(body, g0=g0),
        (jnp.full((1, 128), NEG_INF, jnp.float32), zero,
         (zero,) * nkv if vpu else jnp.zeros((nkv, 128), jnp.float32)))
    g_ref[0] = g0 + num_chunks
    if vpu:
        for h in range(nkv):
            acc_ref[0, h:h + 1, :] = acc[h]
    else:
        acc_ref[0] = acc
    m_ref[0] = jnp.broadcast_to(m, (8, 128))
    l_ref[0] = jnp.broadcast_to(l, (8, 128))


def lanes_flash(q, k_cache, v_cache, layer, page_table, hist_lens, qpk,
                interpret, vpu: bool):
    """_lanes_kernel behind attention._hist_flash_pallas's interface."""
    assert qpk == 1, "the lanes forms are written for one query row a head"
    b, nkv, d = q.shape
    page = k_cache.shape[3]
    ppc = attention.pages_per_chunk(page, nkv, d, 2)
    blk = pl.BlockSpec((1, nkv, 128), lambda i, *_: (i, 0, 0))
    stat = pl.BlockSpec((1, 8, 128), lambda i, *_: (i, 0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    buf = pltpu.VMEM((SLOTS, nkv, ppc, page, 128), k_cache.dtype)
    acc, m, l = pl.pallas_call(
        functools.partial(_lanes_kernel, page_size=page, vpu=vpu),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b,),
            in_specs=[blk, any_spec, any_spec],
            out_specs=(blk, stat, stat),
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, SLOTS)),
                            pltpu.SMEM((1,), jnp.int32),
                            pltpu.SMEM((3,), jnp.int32)]),
        out_shape=(jax.ShapeDtypeStruct((b, nkv, 128), jnp.float32),)
        + (jax.ShapeDtypeStruct((b, 8, 128), jnp.float32),) * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), page_table, hist_lens, q,
      k_cache, v_cache)
    return (acc.reshape(b, nkv, 1, d), l[:, 0, :nkv].reshape(b, nkv, 1, 1),
            m[:, 0, :nkv].reshape(b, nkv, 1, 1))


# -- the turns ----------------------------------------------------------------

def _one_tile(x, rows: int):
    """A part's stand-in: the first rows of its operand, so the part's loads
    and products go and the dependence stays."""
    return x[:rows].astype(jnp.float32)


def _no_fetch(*_args, **_kw):
    """attention._fetch_pipeline's stand-in: nothing is copied or awaited,
    the turns multiply what the buffers hold (zeros)."""
    def nop(*_a):
        return None
    return nop, nop, nop


def flash_of(turn: str, interpret: bool):
    """(the history's flash triple as _hist_flash_pallas returns it, whether
    its output is the attention's) for a turn's name: a base (``rows``,
    ``heads``, ``lanes-vpu``, ``lanes-mxu``) and the parts taken out of
    it, ``+`` between them."""
    base, *parts = turn.split("+")

    def flash(q, k, v, layer, table, hist, qpk):
        saved = (attention.reader_turn, attention._heads_scores,
                 attention._heads_values, attention._fetch_pipeline)
        attention.reader_turn = lambda *_: base
        if "noscores" in parts:
            attention._heads_scores = (
                lambda q_all, k_all, nkv, qpk, rows: jnp.concatenate(
                    [_one_tile(k_all, rows)] * (k_all.shape[0] // nkv // 128),
                    axis=1))
        if "novalues" in parts:
            attention._heads_values = (
                lambda p, v_of, nkv, qpk: p[:, :128] + _one_tile(v_of(0), 1))
        if "nofetch" in parts:
            attention._fetch_pipeline = _no_fetch
        try:
            if base.startswith("lanes-"):
                return lanes_flash(q, k, v, layer, table, hist, qpk,
                                   interpret, vpu=base == "lanes-vpu")
            return attention._hist_flash_pallas(q, k, v, layer, table, hist,
                                                qpk, interpret)
        finally:
            (attention.reader_turn, attention._heads_scores,
             attention._heads_values, attention._fetch_pipeline) = saved

    return flash, not parts


def bench(nkv: int, qpk: int, tokens: list[int], page: int, layers: int,
          turn: str, interpret: bool, reps: int) -> dict:
    d, b = 128, len(tokens)
    per_row = -(-max(tokens) // page)
    pages = b * per_row + 1
    key = jax.random.key(nkv * 131 + qpk)
    kk, kv, kq, kw = jax.random.split(key, 4)
    pool = (layers, nkv, pages, page, d)
    k = jax.random.normal(kk, pool, jnp.bfloat16)
    v = jax.random.normal(kv, pool, jnp.bfloat16)
    q = jax.random.normal(kq, (b, nkv * qpk, d), jnp.bfloat16)
    rng = np.random.default_rng(0)
    table = jnp.asarray(
        (rng.permutation(pages - 1) + 1).reshape(b, per_row), jnp.int32)
    hist = jnp.asarray(tokens, jnp.int32)
    flash, exact = flash_of(turn, interpret)

    @jax.jit
    def walk(q, k, v):
        def one(q, layer):
            num, l, m = flash(q, k, v, layer, table, hist, qpk)
            # The next call's queries hang on this call's every output.
            tie = (num.reshape(q.shape) + l.reshape(b, -1, 1)
                   + m.reshape(b, -1, 1)) * 1e-30
            return (q + tie).astype(q.dtype), None
        return jax.lax.scan(one, q, jnp.arange(layers))[0]

    walk(q, k, v).block_until_ready()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = walk(q, k, v)
        out.block_until_ready()
        best = min(best, (time.perf_counter() - t0) / (reps * layers))
    ppc = attention.pages_per_chunk(page, nkv, d, 2)
    turns = sum(-(-t // (ppc * page)) for t in tokens)
    line = {"nkv": nkv, "q_per_kv": qpk, "rows": b, "tokens": tokens,
            "page": page, "chunk_tokens": ppc * page, "turn": turn,
            "layers": layers, "ms_per_call": best * 1e3,
            "us_per_turn": best * 1e6 / turns,
            "gb_per_s": 2 * sum(tokens) * nkv * d * 2 / best / 1e9,
            "device": jax.devices()[0].device_kind}
    if exact:
        kwin, vwin, ks, vs = (jax.random.normal(x, s, jnp.bfloat16)
                              for x, s in zip(
            jax.random.split(kw, 4),
            [(nkv, b, WINDOW, d)] * 2 + [(b, nkv, d)] * 2))
        args = (q, k, v, jnp.int32(layers - 1), table, hist, kwin, vwin,
                jnp.int32(2), ks, vs)
        want = model.paged_window_attention_xla(*args, qpk)
        num, l, m = jax.jit(lambda q, k, v: flash(
            q, k, v, jnp.int32(layers - 1), table, hist, qpk))(q, k, v)
        k_extra = jnp.concatenate(
            [kwin.transpose(1, 0, 2, 3), ks[:, :, None, :]], axis=2)
        v_extra = jnp.concatenate(
            [vwin.transpose(1, 0, 2, 3), vs[:, :, None, :]], axis=2)
        mask = jnp.asarray([True, True, False, False, True])  # m = 2
        got = attention._merge_extra(q, num, l, m, k_extra, v_extra, mask,
                                     qpk)
        line["max_diff_from_gather"] = float(jnp.max(jnp.abs(
            got.astype(jnp.float32) - want.astype(jnp.float32))))
    return line


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--geometries", default="16x1,4x1,8x1,32x1,16x2,4x7")
    ap.add_argument("--tokens", default="200,400,650,1000")
    ap.add_argument("--turns", default="rows,heads")
    ap.add_argument("--page", type=int, default=16)
    ap.add_argument("--layers", type=int, default=48)
    ap.add_argument("--reps", type=int, default=10)
    args = ap.parse_args()
    interpret = jax.devices()[0].platform == "cpu"
    tokens = [int(t) for t in args.tokens.split(",")]
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as out:
        for geometry in args.geometries.split(","):
            nkv, qpk = (int(x) for x in geometry.split("x"))
            for turn in args.turns.split(","):
                base, *parts = turn.split("+")
                if base.startswith("lanes-") and qpk != 1 or (
                        base != "heads" and set(parts) - {"nofetch"}):
                    continue    # a form or a part that turn does not have
                line = bench(nkv, qpk, tokens, args.page, args.layers, turn,
                             interpret, args.reps)
                print(json.dumps(line), flush=True)
                out.write(json.dumps(line) + "\n")
                out.flush()


if __name__ == "__main__":
    main()
