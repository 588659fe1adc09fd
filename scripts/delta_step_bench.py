#!/usr/bin/env python3
"""The delta rule's decode kernel alone on the device this process holds:
``recurrence.delta_state_step`` by form, the table in PERF.md section 6 (PR
52).

    chiprun -- python3 scripts/delta_step_bench.py
        [--forms early,late,mxu,copies,xla] [--layers 9] [--slots 32]
        [--live 18] [--heads 64] [--dim 128] [--reps 20]

A call is one layer's update of the ``--live`` live slots' state [heads,
dim, dim] float32; ``--layers`` calls run in a loop whose queries take a
(vanishing) share of every call's output (XLA hoists what a loop does not
depend on). One JSON line a form into ``chiprun_out/delta_step.jsonl`` and
stdout: milliseconds a step of ``--layers`` layers (the least of three
loops' mean), the GB/s that is of 2 x the live rows' state, and the largest
difference of y and of the state from ``hybrid.delta_update``.

Forms: ``early`` is the kernel's own (three sums over lanes a head: v
turned to a column, ``S' k`` and ``S' q``, both reads of the ONE decayed
state ahead of the write, ``y = S' q + d (k . q)``). The others are the
forms that lost and the parts, kept HERE so that the table can be read
again (one v5e, 9 layers, 18 live rows of 32, 64 heads of 128 x 128; my
chip run, PR 52, call 3: early 2.19 ms a step, late 2.30, mxu 4.62,
copies 2.19, xla 7.06):

- ``late``: ``y = S q`` read from the NEW state behind the write (the same
  sums, a longer chain: the kernel's first draft);
- ``mxu``: ``S' k`` and ``S' q`` as ONE product on the MXU, the decayed
  state the streamed operand and [k; q] the other, float32 at the highest
  precision;
- ``copies``: no arithmetic, a row's state in and out: the walk's floor
  (its outputs are wrong on purpose: no difference is reported);
- ``xla``: ``hybrid.delta_update`` over every slot of the layer's slice.

On the CPU the kernels are interpreted: ``--layers 2 --slots 4 --live 3
--heads 4 --dim 16 --reps 1`` says that the script runs, never a time."""

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

from dynamo_tpu.engine import hybrid, recurrence              # noqa: E402


def kernel_of(form: str):
    """``recurrence._delta_kernel`` with its arithmetic as ``form`` has it."""
    def kernel(layer_ref, slots_ref, count_ref, beta_ref, decay_ref, k_ref,
               q_ref, v_ref, s_in, s_out, y_ref, buf, sems, *, per_copy):
        del s_in
        p = buf.shape[2]
        eye = recurrence._eye(p, v_ref.shape[2])
        y_ref[...] = jnp.zeros_like(y_ref)

        def block_of(slot, k, at, before, after):
            for j in range(per_copy):
                h = at * per_copy + j
                before(j)
                row = lambda ref: ref[slot, pl.ds(h, 1), :]  # noqa: E731
                if form == "copies":
                    after(j)
                    continue
                key, query = row(k_ref), row(q_ref)
                decayed = buf[k, h] * row(decay_ref)
                v = jnp.sum(jnp.where(eye, row(v_ref), 0.0), axis=-1,
                            keepdims=True)
                if form == "mxu":
                    both = jax.lax.dot_general(
                        decayed, jnp.concatenate(
                            [key, query, jnp.zeros((6, key.shape[1]),
                                                   jnp.float32)], axis=0),
                        (((1,), (1,)), ((), ())),
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)     # [P, 8]
                    read, early = both[:, 0:1], both[:, 1:2]
                else:
                    read = jnp.sum(decayed * key, axis=-1, keepdims=True)
                d = beta_ref[slot, h] * (v - read)
                s = decayed + d * key
                buf[k, h] = s
                if form == "late":
                    out = jnp.sum(s * query, axis=-1, keepdims=True)
                else:
                    out = early + d * jnp.sum(key * query, axis=-1,
                                              keepdims=True)
                y_ref[slot, pl.ds(h, 1), :] = jnp.sum(
                    jnp.where(eye, out, 0.0), axis=0, keepdims=True)
                after(j)

        recurrence._visits(layer_ref[0], count_ref[0], slots_ref, s_out, buf,
                           sems, per_copy, per_copy, block_of)
    return kernel


@functools.partial(jax.jit, static_argnames=("form", "interpret"),
                   donate_argnums=(0,))
def step_as(state, layer, slots, count, decay, k, q, v, beta, form: str,
            interpret: bool):
    """``recurrence.delta_state_step`` with ``kernel_of(form)`` inside."""
    state, y = recurrence._launch(
        kernel_of(form), "delta_" + form, state, layer, slots, count, beta,
        (decay, k, q, recurrence._lane_tiles(v)), interpret)
    return state, y[:, :, :v.shape[2]]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--forms", default="early,late,mxu,copies,xla")
    ap.add_argument("--layers", type=int, default=9)
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--live", type=int, default=18)
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args(argv)
    interpret = jax.devices()[0].platform != "tpu"
    m, b, n, d = args.layers, args.slots, args.heads, args.dim
    keys = jax.random.split(jax.random.key(52), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(keys[0], (b, n, d))) * d ** -0.5
    k = unit(jax.random.normal(keys[1], (b, n, d)))
    v = jax.random.normal(keys[2], (b, n, d))
    live = jnp.asarray(np.random.default_rng(0).permutation(b) < args.live)
    on = live[:, None, None]
    g = jnp.where(on, -jnp.exp(jax.random.normal(keys[3], (b, n, d))), 0.0)
    beta = jnp.where(live[:, None], 2.0 * jax.nn.sigmoid(
        jax.random.normal(keys[4], (b, n))), 0.0)
    walk = hybrid.live_walk(live)
    moved = 2 * args.live * m * n * d * d * 4
    os.makedirs("chiprun_out", exist_ok=True)
    for form in args.forms.split(","):
        def layer_step(state, layer, q, form=form):
            if form == "xla":
                y, state = hybrid.in_layer(lambda s: hybrid.delta_update(
                    s, q, k, v, g, beta))(state, layer)
            elif form == "early":
                state, y = recurrence.delta_state_step(
                    state, layer, *walk, jnp.exp(g), k, q, v, beta,
                    interpret=interpret)
            else:
                state, y = step_as(state, layer, *walk, jnp.exp(g), k, q, v,
                                   beta, form=form, interpret=interpret)
            return state, y

        @functools.partial(jax.jit, donate_argnums=(0,))
        def step(state, q):
            def one(layer, carry):
                state, q, _ = carry
                state, y = layer_step(state, layer, q)
                return state, q + 1e-30 * y, y
            return jax.lax.fori_loop(0, m, one, (state, q, jnp.zeros_like(v)))

        state = jax.random.normal(keys[5], (m, b, n, d, d), jnp.float32)
        out = {"form": form, "layers": m, "slots": b, "live": args.live,
               "heads": n, "dim": d}
        try:
            if form != "copies":
                want_y, want_s = hybrid.delta_update(state[m - 1], q, k, v, g,
                                                     beta)
                got_s, y = layer_step(state + 0.0, jnp.int32(m - 1), q)
                out["y_diff"] = float(jnp.abs(jnp.where(
                    on, y - want_y, 0.0)).max())
                out["state_diff"] = float(jnp.abs(got_s[m - 1]
                                                  - want_s).max())
                del got_s, want_s
            state, _, y = step(state, q)
            jax.block_until_ready(y)
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(args.reps):
                    state, _, y = step(state, q)
                jax.block_until_ready(y)
                best = min(best, (time.perf_counter() - t0) / args.reps)
            out.update(ms_per_step=best * 1e3,
                       gb_per_s=moved / best / 1e9,
                       finite=bool(jnp.isfinite(y).all()))
        except Exception as exc:  # noqa: BLE001 — a form that does not compile is a finding
            out["error"] = f"{type(exc).__name__}: {str(exc)[:300]}"
        del state
        line = json.dumps(out)
        print(line, flush=True)
        with open("chiprun_out/delta_step.jsonl", "a",
                  encoding="utf-8") as fh:
            fh.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
