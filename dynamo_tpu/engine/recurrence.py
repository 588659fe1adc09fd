"""A decode step of a recurrence over the rows that are LIVE: a Pallas
kernel decays, adds to, writes back and reads the float32 state of one
layer, where it lies. Two forms of the update share ONE walk of the slots
(``_visits``: what is copied when): ``state_step``, the Mamba-2 and lightning
mixers' ``S <- decay S + dx (x) B`` (below), and ``delta_state_step``, the
gated delta rule's, which READS the decayed state before it writes it (at
the end of this text).

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

**The second form** (``hybrid.delta_update`` is the definition): a head's
state S [head_dim (v) rows, state (k) lanes], and on the same ONE visit
``S' = S * e^g`` (a decay a LANE: the row e^g broadcast down the sublanes),
``r = S' k`` and ``S' q`` (two sums over lanes of the ONE decayed state,
columns), ``d = beta (v - r)`` (v turned from its row to a column as dt x is
above), ``S = S' + d (x) k`` written back, ``y = S q = S' q + d (k . q)``
turned to a row: with both reads ahead of the write a row costs what its
copies cost (2.19 ms a step of 9 layers x 18 live rows on one v5e, the
walk without arithmetic 2.19; reading the NEW state by q behind the write
2.30; both reads as one product on the MXU 4.62; XLA's ``delta_update``
over every slot 7.06: scripts/delta_step_bench.py, PERF.md section 6, PR
52). No second read of the state from HBM: a step moves a live row's state
in once and out once, as the first form does, whatever the update reads
in between. The decay, k and q arrive as rows of lanes [slots, heads,
state] and are broadcast down the sublanes for nothing; beta a head is a
scalar from SMEM.

**No third form.** What a row keeps beside S, the last K - 1 inputs of the
mixer's convolution, is stepped by XLA (``hybrid.conv_token``) over a
layer's slice of a TAPS-MAJOR stack [M, K - 1, slots, channels], whose
planes fill their tiles. A kernel over that stack (dense planes, a grid over
blocks of 2,048 channels, the stack held to HBM and aliased) ran within 1 us
a layer of its own copies and won 0.04 ms of a 17.3 ms step with 9 such
layers and 0.06 of 16.9 with 23: the layout was the gain, not the call
(PERF.md section 6, PR 53).
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
#: shows against its bytes. At a head of [128, 256] float32 (the Falcon-H1
#: block: 32 heads in 2 groups) ONE head is a copy, 32 copies a row, two
#: lane tiles a row of S, a group's 16 heads unrolled under one B and C, and
#: the form holds its floor: alone, 12 layers x 17 live rows of 32, 2.689 ms
#: a step against 2.684 for its copies without arithmetic (636 GB/s of the
#: live rows' state read and written, 78 % of the chip's bandwidth; XLA's
#: ``state_update`` over every slot 7.14), and the [64, 128] heads above on
#: the same machine 2.774 against 2.767 (626 GB/s; XLA 6.91); in its cell
#: 2.637 ms a step, 79.2 % of the state's roofline (my chip run, PR 54,
#: call 5; the scratch bench lies in the git-ignored ``_chip/``).
CHUNK_BYTES = 128 << 10
VMEM_LIMIT_BYTES = 32 << 20


def chunk_heads(heads: int, head_bytes: int) -> int:
    """Heads a copy moves: the most that divide ``heads`` within
    CHUNK_BYTES."""
    fits = [d for d in range(1, heads + 1)
            if heads % d == 0 and d * head_bytes <= CHUNK_BYTES]
    return max(fits, default=1)


def _visits(layer, count, slots_ref, s_out, buf, sems, per_copy: int,
            block: int, block_of):
    """The walk both kernels share: rows ``slots_ref[:count]`` of layer
    ``layer`` of ``s_out`` [M, slots, heads, head_dim, state] through the
    row buffers ``buf`` [BUFFERS, heads, head_dim, state] (``sems`` [2,
    BUFFERS, chunks]), a copy ``per_copy`` heads. A row's heads are walked
    a BLOCK of ``block`` heads at a time in a loop that is not unrolled (64
    heads unrolled were 26 s of every start-up: PERF.md section 6, PR 42):
    ``block_of(slot, k, at, before, after)`` updates block ``at`` of buffer
    k in place and calls ``before(j)`` ahead of its j-th head's first read
    (the head's copy has arrived) and ``after(j)`` behind its last write
    (the copy back may start)."""
    heads = buf.shape[1]
    chunks = heads // per_copy

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
            def before(j):
                if j % per_copy == 0:
                    copy(i, (at * block + j) // per_copy, False).wait()

            def after(j):
                if (j + 1) % per_copy == 0:
                    copy(i, (at * block + j) // per_copy, True).start()

            block_of(slot, k, at, before, after)
            return carry

        jax.lax.fori_loop(0, heads // block, heads_of, 0)
        return carry

    jax.lax.fori_loop(0, count, visit, 0)
    for back in range(BUFFERS - 1, 0, -1):
        @pl.when(count >= back)
        def _(back=back):
            each(count - back, True, lambda c: c.wait())


def _eye(p: int, lanes: int):
    """[p, lanes] bool: the select that turns a row of lanes into a column
    of sublanes and back (a sum over the other axis: it adds zeros)."""
    return (jax.lax.broadcasted_iota(jnp.int32, (p, lanes), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (p, lanes), 1))


def _state_kernel(layer_ref, slots_ref, count_ref,      # SMEM prefetch
                  decay_ref,                            # SMEM [B, heads]
                  dx_ref, b_ref, c_ref,                 # VMEM, every row's
                  s_in,                                 # ANY, the same
                  s_out, y_ref,                         # buffer as s_in
                  buf, sems, *, per_copy: int):
    """Every visit of one call. dx_ref and y_ref [B, heads, head_dim in
    whole lane tiles], b_ref and c_ref [B, groups, state], buf [BUFFERS,
    heads, head_dim, state], sems [2, BUFFERS, chunks]. A block of heads is
    a group, or a copy's heads where that is more."""
    del s_in
    _, heads, p, _ = buf.shape
    per_group = heads // b_ref.shape[1]
    block = max(per_group, per_copy)
    eye = _eye(p, dx_ref.shape[2])

    # A row nobody visits reads as zeros: nothing reads a dead row's output.
    y_ref[...] = jnp.zeros_like(y_ref)

    def block_of(slot, k, at, before, after):
        for j in range(block):
            h = at * block + j
            if j % per_group == 0:
                g = at * (block // per_group) + j // per_group
                b_row = b_ref[slot, pl.ds(g, 1), :]          # [1, N]
                c_row = c_ref[slot, pl.ds(g, 1), :]
            before(j)
            # dt x of the head down the sublanes: [P, 1].
            dx = jnp.sum(jnp.where(eye, dx_ref[slot, pl.ds(h, 1), :],
                                   0.0), axis=-1, keepdims=True)
            s = decay_ref[slot, h] * buf[k, h] + dx * b_row    # [P, N]
            buf[k, h] = s
            read = jnp.sum(s * c_row, axis=-1, keepdims=True)  # [P, 1]
            y_ref[slot, pl.ds(h, 1), :] = jnp.sum(
                jnp.where(eye, read, 0.0), axis=0, keepdims=True)
            after(j)

    _visits(layer_ref[0], count_ref[0], slots_ref, s_out, buf, sems,
            per_copy, block, block_of)


def _delta_kernel(layer_ref, slots_ref, count_ref,      # SMEM prefetch
                  beta_ref,                             # SMEM [B, heads]
                  decay_ref, k_ref, q_ref, v_ref,       # VMEM, every row's
                  s_in,                                 # ANY, the same
                  s_out, y_ref,                         # buffer as s_in
                  buf, sems, *, per_copy: int):
    """Every visit of one call of the second form. decay_ref (e^g), k_ref
    and q_ref [B, heads, state], v_ref and y_ref [B, heads, head_dim in
    whole lane tiles], buf [BUFFERS, heads, head_dim, state]. A block of
    heads is a copy's."""
    del s_in
    p = buf.shape[2]
    eye = _eye(p, v_ref.shape[2])
    y_ref[...] = jnp.zeros_like(y_ref)

    def block_of(slot, k, at, before, after):
        for j in range(per_copy):
            h = at * per_copy + j
            before(j)
            row = lambda ref: ref[slot, pl.ds(h, 1), :]  # noqa: E731
            key, query = row(k_ref), row(q_ref)                 # [1, N]
            decayed = buf[k, h] * row(decay_ref)                # [P, N]
            # Both reads of the ONE decayed state, ahead of the write: S q
            # = S' q + d (k . q).
            read = jnp.sum(decayed * key, axis=-1, keepdims=True)
            early = jnp.sum(decayed * query, axis=-1, keepdims=True)
            # v of the head down the sublanes: [P, 1].
            v = jnp.sum(jnp.where(eye, row(v_ref), 0.0), axis=-1,
                        keepdims=True)
            d = beta_ref[slot, h] * (v - read)
            buf[k, h] = decayed + d * key
            out = early + d * jnp.sum(key * query, axis=-1, keepdims=True)
            y_ref[slot, pl.ds(h, 1), :] = jnp.sum(
                jnp.where(eye, out, 0.0), axis=0, keepdims=True)
            after(j)

    _visits(layer_ref[0], count_ref[0], slots_ref, s_out, buf, sems,
            per_copy, per_copy, block_of)


def _launch(kernel, name: str, state: jax.Array, layer: jax.Array,
            slots: jax.Array, count: jax.Array, per_head: jax.Array,
            operands: tuple, interpret: bool):
    """One call of ``kernel`` (``_state_kernel`` | ``_delta_kernel``) over
    ``state`` [M, B, heads, head_dim, state], handed whole and aliased to
    the first output: ``per_head`` [B, heads] goes to SMEM, ``operands``
    (indexed by slot) whole to VMEM in the kernel's order. Returns (state,
    y [B, heads, head_dim in whole lane tiles])."""
    _, rows, heads, p, n = state.shape
    assert per_head.shape == (rows, heads) and state.dtype == jnp.float32
    per_copy = chunk_heads(heads, 4 * p * n)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    scalar = lambda a: jnp.asarray(a, jnp.int32).reshape(1)  # noqa: E731
    return pl.pallas_call(
        functools.partial(kernel, per_copy=per_copy),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      *[whole] * len(operands), any_spec],
            out_specs=(any_spec, whole),
            scratch_shapes=[
                pltpu.VMEM((BUFFERS, heads, p, n), jnp.float32),
                pltpu.SemaphoreType.DMA((2, BUFFERS, heads // per_copy))]),
        out_shape=(jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((rows, heads, p + -p % 128),
                                        jnp.float32)),
        input_output_aliases={4 + len(operands): 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=name,
    )(scalar(layer), slots.astype(jnp.int32), scalar(count), per_head,
      *operands, state)


def _lane_tiles(a: jax.Array) -> jax.Array:
    """a [B, heads, head_dim] with its last axis in whole lane tiles."""
    return jnp.pad(a, ((0, 0), (0, 0), (0, -a.shape[2] % 128)))


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
    state, y = _launch(_state_kernel, "ssm_state_step", state, layer, slots,
                       count, decay, (_lane_tiles(dx), b, c), interpret)
    return state, y[:, :, :dx.shape[2]]


# dtpu: ignore[unregistered-jit] -- inner kernel: only ever traced INSIDE registered runner programs (inlined), never dispatched standalone from the serving loop
@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_state_step(state: jax.Array, layer: jax.Array, slots: jax.Array,
                     count: jax.Array, decay: jax.Array, k: jax.Array,
                     q: jax.Array, v: jax.Array, beta: jax.Array,
                     interpret: bool = False):
    """One token of the gated delta rule for the rows ``slots[:count]`` of
    layer ``layer`` of ``state`` [M, B, heads, head_dim (v), state (k)]
    float32, in place: ``S' = S Diag(decay)``, ``S <- S' + beta (v - S' k)
    (x) k``. decay (e^g), k and q [B, heads, state], v [B, heads,
    head_dim], beta [B, heads], all float32 and indexed by slot; ``slots``
    and ``count`` as ``state_step`` takes them. Returns (state, y [B,
    heads, head_dim] float32): ``y = S q`` of the rows visited, zeros
    elsewhere. A slot that is not visited is not touched."""
    state, y = _launch(_delta_kernel, "ssm_delta_step", state, layer, slots,
                       count, beta, (decay, k, q, _lane_tiles(v)), interpret)
    return state, y[:, :, :v.shape[2]]
