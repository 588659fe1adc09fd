"""The expert layer of a routed block where the experts are whole on one
device: two Pallas kernels that read each chosen expert ONCE, as stored, and
never an expert nobody chose (``model.expert_product`` says which a program
takes; ``model.ffn_block`` calls them).

``touched_product``, up to ``model.MOE_DENSE_MAX_ROWS`` rows (a window's
decode step, a verify step, a short chunk): the rows stay as they are and
the kernel walks the held experts that at least one LIVE row chose
(``touched``), one visit an expert: all rows times its matrices, scaled a
row by its gate on that expert (zero where the row did not choose it) and
summed into a float32 output that stays in VMEM over the walk. No sort, no
gather, ONE custom call a layer; its visits are the counter ``moe_touched``.

``pairs_product``, above it: the (row, choice) pairs of a batch are sorted by
the expert HELD here (``model._grouped_experts``) and arrive as ``x`` [M, K]
with ``sizes`` [E] pairs an expert; a pair whose expert is held elsewhere
sorts behind the last group and belongs to none. The kernel walks the
(group, row tile) VISITS a batch has: a row tile of ROW_TILE pairs is
visited once by every group that has a pair in it, so a batch makes at most
M / ROW_TILE + E - 1 visits, an expert nobody chose makes none and is never
copied, and a tile that holds no group's pair is never visited (its rows
come back as whatever the output buffer held: the caller zeroes them). Two
calls a layer (gate and up with the activation, then down).

The weights are the model's stacks over ALL layers, [L, E, K, N], with the
layer's index as a scalar the index maps read: inside a program's layer
scan a layer's slice handed to a custom call is a COPY of its experts
(``model.scan_layers``, ``whole_experts``). A weight tile is copied as
stored. An int8 tile is converted to bfloat16 INSIDE the product's
expression (``_product``: the VPU converts a piece of the contraction under
the MXU's product of the one before, and the next visit's copies run under
both: a visit of ``touched_product`` costs its bytes at
the rate XLA's own weight fusions stream, 740 GB/s on one v5e; converted
whole ahead of the product, as until PR 56, a visit cost 12 us where its
copies take 7), the product accumulates in float32 over the whole K, and
the expert's per-output-channel scale multiplies the finished tile in
float32: the arithmetic of ``model.mm``'s grouped use, with no bfloat16
copy of an expert anywhere and no [E, rows, out] intermediate.

An expert is three matrices or two, read from the operands: two stacks
with an activation are the gate and up of a SwiGLU / ReGLU expert (one
call emits act(x Wg) * (x Wu)), ONE stack with an activation is the up of a
two-matrix expert (act(x W), "relu2": the square of the ReLU), one
without is any expert's way down (x W in float32). A width that is no
whole number of lane tiles (1,856 = 14.5) is read as the chip HOLDS it,
which is W^T (``lies_turned``): tiles [out tile, K] of the transposed
stack, a bitcast, and the product contracts both operands' last
dimension. ``out_tile`` says which tile such a width takes.

The grouped scheme (group metadata as scalar prefetch, a dynamic grid over
the visits, a store masked to the group's rows) is that of
jax.experimental.pallas.ops.tpu.megablox.gmm; what differs is the int8
right side with its scale, the activation fused behind the product (and the
gate's with the up's), and that K is never tiled (an expert's K is 768 to
7,168: a whole column block fits).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Pairs a row tile holds: the MXU's edge. A smaller tile leaves the array
#: as busy (a weight tile is latched for 128 rows' time whatever streams
#: through), a larger one multiplies more rows that are not the group's.
ROW_TILE = 128
#: Elements of one weight tile [K, out tile]: 2 MiB as int8. Three matrices
#: with two copies in flight each are 12 MiB of VMEM (a two-matrix expert's
#: whole width of 1,856: 20).
#: On one v5e a layer at 256 | 512 | 2,048 rows took 0.94 | 1.14 | 3.23 ms
#: at 1 MiB and 0.92 | 1.11 | 3.13 at 2 (64 experts of 2,560 x 768; the two
#: shares' widths the same 2 to 5 %); row tiles of 256 cost 1.41 | 1.59 |
#: 3.57 (PERF.md section 6, PR 40, call 1).
TILE_ELEMS = 1 << 21
VMEM_LIMIT_BYTES = 32 << 20


def out_tile(k: int, n: int) -> int:
    """The widest tile of whole lanes that divides ``n`` outputs and keeps
    a [k, tile] weight block within TILE_ELEMS; ``n`` itself, the WHOLE
    width in one tile, where it is no multiple of a lane tile: a two-matrix
    expert's up of 2,688 x 1,856 (one 5 MB int8 block and its copy in
    flight), and a toy's widths. The other layout, whole-lane tiles with a
    ragged last one (``pl.cdiv`` takes it), fetches a visit's rows once a
    tile and multiplies 1,920 columns: one layer of 32 held of 128 such
    experts on one v5e at 256 | 512 | 1,024 | 4,096 rows took 0.784 | 0.924
    | 1.277 | 4.498 ms whole, 0.798 | 0.940 | 1.298 | 4.552 in tiles of 640
    and 0.810 | 0.954 | 1.316 | 4.604 in tiles of 384, against the masked
    product's 0.972 | 1.981 | 4.775 | 17.81 (PERF.md section 6, PR 43,
    call 1, the conversion still ahead of the product; with it inside,
    whole: 0.649 | - | 1.134 | 4.352, PR 56, call 2).
    ``touched_product`` takes its tile of an expert's WIDTH by the same
    rule (768 of 768 under 2,560 rows, 768 of 1,536 under 2,048, 512 of
    4,096, 256 of 1,280 under 4,096 and of 2,048 under 7,168, 1,856 whole):
    a visit of the last but one kind copies [7,168, 256] blocks, 256 bytes
    a row, and is the one shape whose walk over ALL its experts reads
    slower than the masked product (1.00 | 0.95 ms, PR 56, call 1)."""
    if n % 128:
        return n
    fits = [d for d in range(128, n + 1, 128)
            if n % d == 0 and k * d <= TILE_ELEMS]
    return max(fits, default=128)


def lies_turned(k: int, n: int) -> bool:
    """Whether the chip holds a stack [L, E, k, n] with k as its minor
    dimension: the TPU's default layout of an array is a function of its
    shape, and where the last dimension is no whole number of lane tiles
    and the one before it is, it puts that one minor ({2,3,1,0}: W^T,
    unpadded). Handed to a custom call as [.., k, n] such a stack is first
    COPIED whole into rows of n (3.7 GB for 23 x 32 x 2,688 x 1,856;
    tests/test_tpu_compile.py holds the program to no such copy); its
    transpose is a bitcast, and the kernel multiplies by tiles [tn, k]."""
    return n % 128 != 0 and k % 128 == 0


def visits(sizes: jax.Array, m: int):
    """The walk over ``m`` rows sorted by group, ``sizes`` int32 [E] rows a
    group: (offsets [E + 1], group [V], tile [V], count), all int32, V =
    m / ROW_TILE + E - 1 the most visits there can be (entries from
    ``count`` on mean nothing and are never read). Visit v < count
    multiplies the rows of row tile ``tile[v]`` that lie in [offsets[g],
    offsets[g + 1]) by expert g = ``group[v]``; visits are ordered by
    group, a group's by tile, so a row tile's visits are consecutive and so
    are a group's."""
    e, tm = sizes.shape[0], ROW_TILE
    ends = jnp.cumsum(sizes)
    first = (ends - sizes) // tm
    per_group = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(per_group)
    v = jnp.arange(m // tm + e - 1, dtype=jnp.int32)
    # The group of visit v: as many groups as end at or before it.
    group = jnp.sum(upto[None, :] <= v[:, None], axis=1)
    tile = first[group] + v - (upto - per_group)[group]
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return tuple(a.astype(jnp.int32) for a in (offsets, group, tile,
                                               upto[-1]))


def _product(lhs, w_ref, turned: bool, quant: bool) -> jax.Array:
    """lhs [T, C] bfloat16 (a ref or a value) times a weight tile [C, N]
    ([N, C] where it lies ``turned``), float32. An int8 tile is converted
    INSIDE the product's expression, so no bfloat16 copy of it is kept: the
    compiler converts a piece of the contraction under the product of the
    one before (a loop of our own over 256 | 512 | 1,024 columns read the
    same 0.435 | 0.438 | 0.438 ms a SmallThinker layer as this, 0.436; PR
    56, call 1)."""
    w = w_ref[...]
    if quant:
        w = w.astype(jnp.bfloat16)
    return jax.lax.dot_general(
        lhs[...], w, (((1,), (1 if turned else 0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _unit(y: jax.Array, act: str) -> jax.Array:
    """model._gate_act on a float32 product: bfloat16."""
    if act == "silu":
        return jax.nn.silu(y).astype(jnp.bfloat16)
    y = jnp.maximum(y, 0.0)
    return (jnp.square(y) if act == "relu2" else y).astype(jnp.bfloat16)


def _products(lhs, ws, scales, turned, act: str | None) -> jax.Array:
    """lhs [T, C] times one expert's weight tiles ``ws`` (``_product``;
    ``scales``: their [1, N] float32 scales where they are int8, else None;
    ``turned``: which of them lie turned): two give ``act``(x Wg) * (x Wu),
    one with an ``act`` gives ``act``(x W), both bfloat16
    (model._gate_act's arithmetic); one without gives x W in float32."""
    quant = scales is not None
    ys = [_product(lhs, w, t, quant) for w, t in zip(ws, turned)]
    if quant:
        ys = [y * s[...] for y, s in zip(ys, scales)]
    if len(ws) == 2:
        return _unit(ys[0], act) * ys[1].astype(jnp.bfloat16)
    return _unit(ys[0], act) if act else ys[0]


def _pairs_kernel(layer_ref, offsets_ref, group_ref, tile_ref,  # SMEM
                  x_ref, *rest, n_w: int, quant: bool, act: str | None,
                  turned: bool):
    """One visit and output tile. rest: ``n_w`` weight tiles [K, tn] (gate
    and up, or one matrix; [tn, K] where the stack lies ``turned``), their
    scales [1, tn] if ``quant``, the output tile [tm, tn] (layer_ref is
    the index maps' alone): ``_products`` of the visit's rows, stored where
    the rows are the group's."""
    ws, rest = rest[:n_w], rest[n_w:]
    scales, out_ref = (rest[:n_w], rest[n_w]) if quant else (None, rest[0])
    v = pl.program_id(1)
    g = group_ref[v]
    y = _products(x_ref, ws, scales, (turned,) * n_w, act)
    tm = x_ref.shape[0]
    row = tile_ref[v] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
    out_ref[...] = jnp.where(mine, y.astype(out_ref.dtype), out_ref[...])


# dtpu: ignore[unregistered-jit] -- inner kernel: only ever traced INSIDE registered runner programs (inlined), never dispatched standalone from the serving loop
@functools.partial(jax.jit, static_argnames=("act", "interpret"))
def pairs_product(x: jax.Array, ws: tuple, scales: tuple | None,
                  layer: jax.Array, walk: tuple, act: str | None = None,
                  interpret: bool = False) -> jax.Array:
    """x [M, K] bfloat16, sorted by group, M a multiple of ROW_TILE, times
    each row's own matrix of layer ``layer`` of ``ws``: two stacks [L, E, K,
    N] (returns ``act``(x Wg) * (x Wu), bfloat16: the gated unit of a
    SwiGLU / ReGLU expert), one with an ``act`` (``act``(x W), bfloat16:
    the unit of a two-matrix expert, "relu2") or one without (x W, float32
    [M, N]: an expert's way down). The stacks are the model's over ALL
    layers and ``layer`` (int32 scalar) is read by the index maps: a
    layer's slice taken ahead of a custom call is a COPY of its experts
    (XLA fuses such a slice into its own products alone).
    ``scales``: the stacks' float32 [L, E, 1, N] where they are int8, else
    None. ``walk``: ``visits`` of the rows a group; rows past their sum,
    and every row of a tile no group reaches, come back undefined. Its own
    jit: callers whose operands have one shape share ONE trace of the
    kernel, as attention._latent_flash."""
    m, k = x.shape
    n = ws[0].shape[3]
    tm, tn = ROW_TILE, out_tile(k, n)
    quant = scales is not None
    turned = lies_turned(k, n)
    if turned:      # a bitcast of the stack as the chip holds it
        ws = tuple(jnp.swapaxes(w, 2, 3) for w in ws)
        w_spec = pl.BlockSpec((None, None, tn, k),
                              lambda j, v, l, o, g, t: (l[0], g[v], j, 0))
    else:
        w_spec = pl.BlockSpec((None, None, k, tn),
                              lambda j, v, l, o, g, t: (l[0], g[v], 0, j))
    s_spec = pl.BlockSpec((None, None, 1, tn),
                          lambda j, v, l, o, g, t: (l[0], g[v], 0, j))
    dtype = jnp.bfloat16 if act else jnp.float32
    *walk, count = walk
    return pl.pallas_call(
        functools.partial(_pairs_kernel, n_w=len(ws), quant=quant, act=act,
                          turned=turned),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            # Output tiles outermost: a group's consecutive visits keep its
            # weight tile in VMEM; the visits are as many as the batch has.
            grid=(pl.cdiv(n, tn), count),
            in_specs=[pl.BlockSpec((tm, k),
                                   lambda j, v, l, o, g, t: (t[v], 0)),
                      *[w_spec] * len(ws),
                      *([s_spec] * len(ws) if quant else [])],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, v, l, o, g, t: (t[v], j))),
        out_shape=jax.ShapeDtypeStruct((m, n), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), *walk, x, *ws,
      *(scales if quant else ()))


def touched(load: jax.Array):
    """The walk of ``touched_product``: the held experts with ``load`` > 0
    ([E], the live rows' picks an expert) in rising order, then zeros, and
    how many they are (int32 [E], int32 scalar). No sort: an expert's place
    is the count of touched experts before it."""
    hit = load > 0
    e = jnp.arange(hit.shape[0], dtype=jnp.int32)
    place = jnp.cumsum(hit.astype(jnp.int32)) - 1
    walk = jnp.sum(jnp.where(hit[None, :] & (place[None, :] == e[:, None]),
                             e[None, :], 0), axis=1, dtype=jnp.int32)
    return walk, jnp.sum(hit, dtype=jnp.int32)


def _touched_kernel(layer_ref, walk_ref,  # SMEM
                    x_ref, gates_ref, *rest, n_w: int, quant: bool, act: str,
                    turned: tuple):
    """One visit (a touched expert) and tile of its width. rest: the
    expert's ``n_w`` weight tiles (gate, up and down, or up and down),
    their scales if ``quant``, the output [T, H] float32, which stays in
    VMEM over the whole grid and is written back once."""
    ws, rest = rest[:n_w], rest[n_w:]
    scales, out_ref = (rest[:n_w], rest[n_w]) if quant else (None, rest[0])
    v = pl.program_id(0)

    @pl.when((v == 0) & (pl.program_id(1) == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    ff = _products(x_ref, ws[:-1], scales and scales[:-1], turned[:-1], act)
    y = _products(ff, ws[-1:], scales and scales[-1:], turned[-1:], None)
    # Row t's gate on this expert: zero where it did not choose it or is
    # not live.
    gates = gates_ref[...]
    mine = jax.lax.broadcasted_iota(jnp.int32, gates.shape, 1) == walk_ref[v]
    out_ref[...] += y * jnp.sum(jnp.where(mine, gates, 0.0), axis=1,
                                keepdims=True)


def touched_product(x: jax.Array, gates: jax.Array, ws: tuple,
                    scales: tuple | None, layer: jax.Array, walk: jax.Array,
                    count: jax.Array, act: str, interpret: bool = False
                    ) -> jax.Array:
    """The expert layer of a few rows (a decode step's) over the experts
    they chose: x [T, K] bfloat16 as it is, ``gates`` [T, E] float32 a
    row's gate on each held expert (zero where it did not choose it, and in
    every row that is not live), ``walk`` the ``count`` held experts that
    have a nonzero column (``touched``). Visit v multiplies ALL T rows by
    expert ``walk[v]`` of layer ``layer`` of ``ws`` (gate, up, down stacks
    [L, E, K, I] and [L, E, I, K], or up and down of a two-matrix expert:
    ``pairs_product``'s operands, whole over all layers, read as stored
    and where they lie ``turned``), scales the rows by their gates and adds
    them into the output: sum over e of gates[:, e] * down_e(``act``(x
    gate_e) * (x up_e)), float32 [T, K]. An expert nobody chose is never
    copied; no sort, no gather, ONE call a layer. An expert wider than a
    weight tile (``out_tile``) is visited a tile of its width at a time:
    the unit's columns [T, tile] times the tile's rows of the way down add
    up over the tiles. With ``count`` 0 the kernel runs no step and the
    result is zeros."""
    t, k = x.shape
    n = ws[0].shape[3]
    quant = scales is not None
    # A weight tile's elements are TILE_ELEMS bytes whatever the leaves are.
    ti = out_tile(k * ws[0].dtype.itemsize, n)
    turned = (*[lies_turned(k, n)] * (len(ws) - 1), lies_turned(n, k))

    def tile(down: bool, flip: bool) -> pl.BlockSpec:
        """The tile of an expert's matrix a visit takes, as stored: every
        row of a tile's columns on the way up, a tile's rows on the way
        down; the other way round in a stack the chip holds turned."""
        block, at = (ti, k) if down else (k, ti), (1, 0) if down else (0, 1)
        if flip:
            block, at = block[::-1], at[::-1]
        return pl.BlockSpec(
            (None, None, *block),
            lambda v, i, l, w: (l[0], w[v], i * at[0], i * at[1]))

    specs = [tile(j == len(ws) - 1, flip) for j, flip in enumerate(turned)]
    ws = tuple(jnp.swapaxes(w, 2, 3) if flip else w
               for w, flip in zip(ws, turned))
    s_specs = [pl.BlockSpec((None, None, 1, ti),
                            lambda v, i, l, w: (l[0], w[v], 0, i))
               ] * (len(ws) - 1) + [
        pl.BlockSpec((None, None, 1, k), lambda v, i, l, w: (l[0], w[v], 0, 0))]
    whole = lambda shape: pl.BlockSpec(shape, lambda v, i, l, w: (0, 0))  # noqa: E731
    out = pl.pallas_call(
        functools.partial(_touched_kernel, n_w=len(ws), quant=quant, act=act,
                          turned=turned),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(count, pl.cdiv(n, ti)),
            in_specs=[whole(x.shape), whole(gates.shape), *specs,
                      *(s_specs if quant else [])],
            out_specs=whole((t, k))),
        out_shape=jax.ShapeDtypeStruct((t, k), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), walk, x, gates, *ws,
      *(scales if quant else ()))
    return jnp.where(count > 0, out, 0.0)
