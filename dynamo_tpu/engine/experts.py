"""The expert layer of a routed block over (row, choice) pairs sorted by
expert: a Pallas kernel multiplies each pair by its OWN expert, reading the
experts as they are stored.

Where ``model.ffn_block`` has more rows than ``MOE_DENSE_MAX_ROWS`` and the
experts are whole on one device, the pairs of a batch are sorted by the
expert HELD here (``model._grouped_experts``) and arrive as ``x`` [M, K]
with ``sizes`` [E] pairs an expert; a pair whose expert is held elsewhere
sorts behind the last group and belongs to none. The kernel walks the
(group, row tile) VISITS a batch has: a row tile of ROW_TILE pairs is
visited once by every group that has a pair in it, so a batch makes at most
M / ROW_TILE + E - 1 visits, an expert nobody chose makes none and is never
copied, and a tile that holds no group's pair is never visited (its rows
come back as whatever the output buffer held: the caller zeroes them).

The weights are the model's stacks over ALL layers, [L, E, K, N], with the
layer's index as a scalar the index maps read: inside a program's layer
scan a layer's slice handed to a custom call is a COPY of its experts
(``model.scan_layers``, ``whole_experts``). A weight tile [K, out tile] is
copied as stored. An int8 tile is converted
to bfloat16 IN VMEM, once a group and output tile (consecutive visits of one
group share the copy AND the conversion: the grid walks the output tiles
outermost, so a group's tile is read from HBM exactly once a call), the
product accumulates in float32 over the whole K, and the expert's
per-output-channel scale multiplies the finished tile in float32: the
arithmetic of ``model.mm``'s grouped use, with no bfloat16 copy of an
expert in HBM and no [E, rows, out] intermediate.

An expert is three matrices or two, read from the operands: two stacks
with an activation are the gate and up of a SwiGLU / ReGLU expert (one
call emits act(x Wg) * (x Wu)), ONE stack with an activation is the up of a
two-matrix expert (act(x W), "relu2": the square of the ReLU), one
without is any expert's way down (x W in float32). A width that is no
whole number of lane tiles (1,856 = 14.5) is read as the chip HOLDS it,
which is W^T (``lies_turned``): tiles [out tile, K] of the transposed
stack, a bitcast, and the product contracts both operands' last
dimension. ``out_tile`` says which tile such a width takes.

The scheme (group metadata as scalar prefetch, a dynamic grid over the
visits, a store masked to the group's rows) is that of
jax.experimental.pallas.ops.tpu.megablox.gmm; what differs is the int8
right side with its scale, the activation fused behind the product (and the
gate's with the up's), and that K is never tiled (an expert's K is 768 to
4,096: a whole column block fits).
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
#: Elements of one weight tile [K, out tile]: 2 MiB as int8. Two matrices,
#: two copies in flight and their bfloat16 conversions are 16 MiB of VMEM.
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
    expert's up of 2,688 x 1,856 (one 5 MB int8 block, its copy in flight
    and a 10 MB bfloat16 conversion: 21 MB of VMEM_LIMIT_BYTES), and a
    toy's widths. The other layout, whole-lane tiles with a ragged last one
    (``pl.cdiv`` takes it), fetches a visit's rows once a tile and
    multiplies 1,920 columns: one layer of 32 held of 128 such experts on
    one v5e at 256 | 512 | 1,024 | 4,096 rows took 0.784 | 0.924 | 1.277 |
    4.498 ms whole, 0.798 | 0.940 | 1.298 | 4.552 in tiles of 640 and
    0.810 | 0.954 | 1.316 | 4.604 in tiles of 384, against the masked
    product's 0.972 | 1.981 | 4.775 | 17.81 (PERF.md section 6, PR 43,
    call 1; at 128 rows 0.591 masked | 0.697: model.MOE_DENSE_MAX_ROWS
    stands for this shape too)."""
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


def _unit(y: jax.Array, act: str) -> jax.Array:
    """model._gate_act on a float32 product: bfloat16."""
    if act == "silu":
        return jax.nn.silu(y).astype(jnp.bfloat16)
    y = jnp.maximum(y, 0.0)
    return (jnp.square(y) if act == "relu2" else y).astype(jnp.bfloat16)


def _pairs_kernel(layer_ref, offsets_ref, group_ref, tile_ref,  # SMEM
                  x_ref, *rest, n_w: int, quant: bool, act: str | None,
                  turned: bool):
    """One visit and output tile. rest: ``n_w`` weight tiles [K, tn] (gate
    and up, or one matrix; [tn, K] where the stack lies ``turned``), their
    scales [1, tn] if ``quant``, the output tile [tm, tn], and if ``quant``
    ``n_w`` bfloat16 buffers of a weight tile's shape that hold the group's
    converted tiles from its first visit on (layer_ref is the index maps'
    alone). Two matrices give ``act``(x Wg) * (x Wu), one with an ``act``
    gives ``act``(x W), both in bfloat16 (model._gate_act's arithmetic);
    one without gives x W in float32."""
    ws, rest = rest[:n_w], rest[n_w:]
    if quant:
        scales, rest = rest[:n_w], rest[n_w:]
    out_ref, bufs = rest[0], rest[1:]
    v = pl.program_id(1)
    g = group_ref[v]
    if quant:
        @pl.when((v == 0) | (g != group_ref[jnp.maximum(v - 1, 0)]))
        def _():
            for w, buf in zip(ws, bufs):
                buf[...] = w[...].astype(jnp.bfloat16)
        ws = bufs
    x = x_ref[...]
    if turned:      # x W as x (W^T)^T: the MXU takes either operand order
        ys = [jax.lax.dot_general(x, w[...], (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
              for w in ws]
    else:
        ys = [jnp.dot(x, w[...], preferred_element_type=jnp.float32)
              for w in ws]
    if quant:
        ys = [y * s[...] for y, s in zip(ys, scales)]
    if n_w == 2:
        gate, up = ys
        y = _unit(gate, act) * up.astype(jnp.bfloat16)
    else:
        y = _unit(ys[0], act) if act else ys[0]
    tm = x.shape[0]
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
                                   lambda j, v, l, o, g, t: (t[v], j)),
            scratch_shapes=([pltpu.VMEM(w_spec.block_shape[2:], jnp.bfloat16)]
                            * len(ws) if quant else [])),
        out_shape=jax.ShapeDtypeStruct((m, n), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), *walk, x, *ws,
      *(scales if quant else ()))
