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

The scheme (group metadata as scalar prefetch, a dynamic grid over the
visits, a store masked to the group's rows) is that of
jax.experimental.pallas.ops.tpu.megablox.gmm; what differs is the int8
right side with its scale, the fused gate and up product, and that K is
never tiled (an expert's K is 768 to 4,096: a whole column block fits).
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
    a [k, tile] weight block within TILE_ELEMS; ``n`` itself where it is no
    multiple of a lane tile (a toy's widths: the CPU interprets any block)."""
    if n % 128:
        return n
    fits = [d for d in range(128, n + 1, 128)
            if n % d == 0 and k * d <= TILE_ELEMS]
    return max(fits, default=128)


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


def _pairs_kernel(layer_ref, offsets_ref, group_ref, tile_ref,  # SMEM
                  x_ref, *rest, n_w: int, quant: bool, act: str | None):
    """One visit and output tile. rest: ``n_w`` weight tiles [K, tn] (gate
    and up, or one matrix), their scales [1, tn] if ``quant``, the output
    tile [tm, tn], and if ``quant`` ``n_w`` bfloat16 buffers [K, tn] that
    hold the group's converted tiles from its first visit on (layer_ref
    is the index maps' alone). Two matrices
    give ``act``(x Wg) * (x Wu) in bfloat16 (model._gate_act's arithmetic),
    one gives x W in float32."""
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
    ys = [jnp.dot(x, w[...], preferred_element_type=jnp.float32) for w in ws]
    if quant:
        ys = [y * s[...] for y, s in zip(ys, scales)]
    if n_w == 2:
        gate, up = ys
        gate = jnp.maximum(gate, 0.0) if act == "relu" else jax.nn.silu(gate)
        y = gate.astype(jnp.bfloat16) * up.astype(jnp.bfloat16)
    else:
        y, = ys
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
    each row's own matrix of layer ``layer`` of ``ws``: one stack [L, E, K,
    N] (returns x W, float32 [M, N]) or two (returns ``act``(x Wg) * (x
    Wu), bfloat16: the gated unit of a SwiGLU / ReGLU expert). The stacks
    are the model's over ALL layers and ``layer`` (int32 scalar) is read by
    the index maps: a layer's slice taken ahead of a custom call is a COPY
    of its experts (XLA fuses such a slice into its own products alone).
    ``scales``: the stacks' float32 [L, E, 1, N] where they are int8, else
    None. ``walk``: ``visits`` of the rows a group; rows past their sum,
    and every row of a tile no group reaches, come back undefined. Its own
    jit: callers whose operands have one shape share ONE trace of the
    kernel, as attention._latent_flash."""
    m, k = x.shape
    n = ws[0].shape[3]
    tm, tn = ROW_TILE, out_tile(k, n)
    quant = scales is not None
    w_spec = pl.BlockSpec((None, None, k, tn),
                          lambda j, v, l, o, g, t: (l[0], g[v], 0, j))
    s_spec = pl.BlockSpec((None, None, 1, tn),
                          lambda j, v, l, o, g, t: (l[0], g[v], 0, j))
    dtype = jnp.bfloat16 if len(ws) == 2 else jnp.float32
    *walk, count = walk
    return pl.pallas_call(
        functools.partial(_pairs_kernel, n_w=len(ws), quant=quant, act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            # Output tiles outermost: a group's consecutive visits keep its
            # weight tile in VMEM; the visits are as many as the batch has.
            grid=(n // tn, count),
            in_specs=[pl.BlockSpec((tm, k),
                                   lambda j, v, l, o, g, t: (t[v], 0)),
                      *[w_spec] * len(ws),
                      *([s_spec] * len(ws) if quant else [])],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, v, l, o, g, t: (t[v], j)),
            scratch_shapes=([pltpu.VMEM((k, tn), jnp.bfloat16)] * len(ws)
                            if quant else [])),
        out_shape=jax.ShapeDtypeStruct((m, n), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), *walk, x, *ws,
      *(scales if quant else ()))
