"""A decode step of the Mamba-2 recurrence over the rows that are LIVE: a
Pallas kernel decays, adds to, writes back and reads by C the float32 state
of one layer, where it lies.

``hybrid.ssm_step`` is the definition: ``S <- exp(dt A) S + (dt x) (x) B``,
``y = S C``, a head of ``head_dim`` rows over ``state`` lanes, the heads of
a group under one B and one C. XLA computes it over every slot (a dead slot
is multiplied by exp(0) and written back) and in two fusions that each read
the old state. Here the state is the runner's stack over ALL recurrent
layers and ALL slots, [M, slots, heads, head_dim, state], handed over whole
and aliased to the output (a layer's slice ahead of a custom call is a COPY
of it; the layer's index is a scalar the copies read, as the experts' stack
in engine/experts.py): the kernel walks ``slots[:count]``, brings a row's
state into VMEM in chunks of heads, computes in place, reads the new state
by C in the same pass and sends the chunk back where it came from. A slot
that is not in the list is neither read nor written. Three row buffers: the
next row's state arrives and the last row's leaves while this one is
computed.

The few KB a row that the state is updated from (the decay, dt x, B, C) are
XLA's, as in ``ssm_step``, and arrive as XLA holds them: a head's dt x is a
ROW of head_dim lanes and the update wants it down the sublanes, its sums
by C come out down the sublanes and y wants them in a row. Both turns are a
select against the identity and a sum (over lanes: the row's diagonal
matrix summed to a column; over sublanes: the column's back to a row), each
exact: it adds zeros. On one v5e that costs less than a head's column
sliced out of a transposed [head_dim, heads] tile and broadcast along the
lanes, and far less than products by 1 on the MXU (PERF.md section 6, PR
42).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Row buffers: one arriving, one computed, one leaving.
BUFFERS = 3
#: Bytes of state one copy moves: 128 KB is 4 heads of [64, 128] float32.
#: The next row's copies start when this row's visit does, so the reads
#: pause for as long as a row's last chunk is computed: on one v5e, 18 live
#: rows of 32 over 23 layers, the first kernel (every head unrolled) took
#: 2.89 ms a step at 128 KB, 3.01 at 256, 3.09 at 512, 3.25 at 2 MB (the
#: copies alone 2.83 to 2.87 at every size; this one 2.92 at 128 KB;
#: PERF.md section 6, PR 42). Under 128 KB a copy's issue (58 ns, PR 37)
#: shows against its bytes.
CHUNK_BYTES = 128 << 10
VMEM_LIMIT_BYTES = 32 << 20


def chunk_heads(heads: int, head_bytes: int) -> int:
    """Heads a copy moves: the most that divide ``heads`` within
    CHUNK_BYTES."""
    fits = [d for d in range(1, heads + 1)
            if heads % d == 0 and d * head_bytes <= CHUNK_BYTES]
    return max(fits, default=1)


def _state_kernel(layer_ref, slots_ref, count_ref,      # SMEM prefetch
                  decay_ref,                            # SMEM [B, heads]
                  dx_ref, b_ref, c_ref,                 # VMEM, every row's
                  s_in,                                 # ANY, the same
                  s_out, y_ref,                         # buffer as s_in
                  buf, sems, *, per_copy: int):
    """Every visit of one call. dx_ref and y_ref [B, heads, head_dim in
    whole lane tiles], b_ref and c_ref [B, groups, state], buf [BUFFERS,
    heads, head_dim, state], sems [2, BUFFERS, chunks]. A row's heads are
    walked a BLOCK at a time (a group, or a copy's heads where that is
    more) in a loop that is not unrolled: 64 heads unrolled were 26 s of
    every start-up (PERF.md section 6, PR 42)."""
    del s_in
    layer, count = layer_ref[0], count_ref[0]
    _, heads, p, _ = buf.shape
    per_group = heads // b_ref.shape[1]
    chunks = heads // per_copy
    block = max(per_group, per_copy)
    lanes = dx_ref.shape[2]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (p, lanes), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (p, lanes), 1))

    def copy(i, chunk, out: bool):
        """Visit i's chunk between the slot's state and its buffer."""
        k = i % BUFFERS
        span = pl.ds(chunk * per_copy, per_copy)
        hbm, vmem = s_out.at[layer, slots_ref[i], span], buf.at[k, span]
        src, dst = (vmem, hbm) if out else (hbm, vmem)
        return pltpu.make_async_copy(src, dst, sems.at[int(out), k, chunk])

    def each(i, out: bool, do):
        def one(chunk, carry):
            do(copy(i, chunk, out))
            return carry
        jax.lax.fori_loop(0, chunks, one, 0)

    # A row nobody visits reads as zeros: nothing reads a dead row's output.
    y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(count > 0)
    def _():
        each(0, False, lambda c: c.start())

    def visit(i, carry):
        slot, k = slots_ref[i], i % BUFFERS

        # The buffer the next row arrives in left at visit i - 2.
        @pl.when(i >= BUFFERS - 1)
        def _():
            each(i - (BUFFERS - 1), True, lambda c: c.wait())

        @pl.when(i + 1 < count)
        def _():
            each(i + 1, False, lambda c: c.start())

        def heads_of(at, carry):
            for j in range(block):
                h = at * block + j
                if j % per_group == 0:
                    g = at * (block // per_group) + j // per_group
                    b_row = b_ref[slot, pl.ds(g, 1), :]          # [1, N]
                    c_row = c_ref[slot, pl.ds(g, 1), :]
                if j % per_copy == 0:
                    copy(i, h // per_copy, False).wait()
                # dt x of the head down the sublanes: [P, 1].
                dx = jnp.sum(jnp.where(eye, dx_ref[slot, pl.ds(h, 1), :],
                                       0.0), axis=-1, keepdims=True)
                s = decay_ref[slot, h] * buf[k, h] + dx * b_row    # [P, N]
                buf[k, h] = s
                read = jnp.sum(s * c_row, axis=-1, keepdims=True)  # [P, 1]
                y_ref[slot, pl.ds(h, 1), :] = jnp.sum(
                    jnp.where(eye, read, 0.0), axis=0, keepdims=True)
                if (j + 1) % per_copy == 0:
                    copy(i, h // per_copy, True).start()
            return carry

        jax.lax.fori_loop(0, heads // block, heads_of, 0)
        return carry

    jax.lax.fori_loop(0, count, visit, 0)
    for back in range(BUFFERS - 1, 0, -1):
        @pl.when(count >= back)
        def _(back=back):
            each(count - back, True, lambda c: c.wait())


# dtpu: ignore[unregistered-jit] -- inner kernel: only ever traced INSIDE registered runner programs (inlined), never dispatched standalone from the serving loop
@functools.partial(jax.jit, static_argnames=("interpret",))
def state_step(state: jax.Array, layer: jax.Array, slots: jax.Array,
               count: jax.Array, decay: jax.Array, dx: jax.Array,
               b: jax.Array, c: jax.Array, interpret: bool = False):
    """One token of the recurrence for the rows ``slots[:count]`` of layer
    ``layer`` (int32 scalar) of ``state`` [M, B, heads, head_dim, state]
    float32, in place: ``S <- decay S + dx (x) B``. decay [B, heads], dx
    [B, heads, head_dim] (dt x), b and c [B, groups, state], all float32
    and indexed by slot; ``slots`` [B] int32, distinct in its first
    ``count`` (int32 scalar) entries, the rest never read. Returns (state,
    y [B, heads, head_dim] float32): ``y = S C`` of the rows visited,
    zeros elsewhere. A slot that is not visited is not touched."""
    _, rows, heads, p, n = state.shape
    assert decay.shape == (rows, heads) and state.dtype == jnp.float32
    per_copy = chunk_heads(heads, 4 * p * n)
    lanes = p + -p % 128
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    scalar = lambda a: jnp.asarray(a, jnp.int32).reshape(1)  # noqa: E731
    state, y = pl.pallas_call(
        functools.partial(_state_kernel, per_copy=per_copy),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), whole, whole,
                      whole, any_spec],
            out_specs=(any_spec, whole),
            scratch_shapes=[
                pltpu.VMEM((BUFFERS, heads, p, n), jnp.float32),
                pltpu.SemaphoreType.DMA((2, BUFFERS, heads // per_copy))]),
        out_shape=(jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((rows, heads, lanes), jnp.float32)),
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="ssm_state_step",
    )(scalar(layer), slots.astype(jnp.int32), scalar(count), decay,
      jnp.pad(dx, ((0, 0), (0, 0), (0, lanes - p))), b, c, state)
    return state, y[:, :, :p]
