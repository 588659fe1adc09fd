"""Plain reference of a looped stack (ByteDance/Ouro-2.6B, ``model_type``
``ouro``; arXiv:2510.25741): the whole forward over ``prompt +
generated[:-1]``, every pass over every layer, no cache, no pool, no
window. ``jax.numpy`` only, float32, ``highest`` precision, over the
parameters as stored (int8 leaves dequantised: q * s); nothing of engine/
but the spec's numbers. ``plain``, ``rms_norm``, ``rope`` and the head are
lib/reference.py's; the loop over passes is this file's own
(``teacher_forced`` there scans the layers once and norms once).

With ``g1..g4`` a layer's four RMSNorm weights and ``gF`` the final norm's::

    x = E[token]                                  # no scaling
    for t in 0..3:                                # total_ut_steps, SAME layers
      for l in 0..47:
        h  = RMS(x; g1_l)
        q, k, v = h Wq_l, h Wk_l, h Wv_l          # 16 heads of 128, no bias
        q, k = rope(q), rope(k)                   # rotate-half, theta 1e6
        a  = softmax(q K^T / sqrt(128), causal) V # K, V of THIS pass alone
        x  = x + RMS(a Wo_l; g2_l)                # the sublayer's OUTPUT normed
        h2 = RMS(x; g3_l)
        x  = x + RMS((silu(h2 Wg_l) * (h2 Wu_l)) Wd_l; g4_l)
      x = RMS(x; gF)                              # after EVERY pass
    logits = x W_head                             # of the last pass, untied

A full forward needs no K or V but its own: pass t of layer l attends what
pass t of layer l computed for the earlier tokens, which is what it has. The
exit gate is not evaluated (``early_exit_threshold`` 1: only the last pass
reaches it). Assumed, as the configuration's file lists: where the sandwich
norms sit, the final norm after every pass, no QKV bias, rotate-half RoPE.
The leaves lie as engine/model.py ``param_shapes`` says: ``input_norm``
(g1), ``attn_out_gain`` (g2, a column [2048, 1]), ``post_attn_norm`` (g3),
``mlp_out_gain`` (g4, a column): the weight law draws a ``*_norm`` leaf as
ones and a column normal / sqrt(2048), so the gains of the OUTPUT norms are
small and signed, a 45th in rms (``param_shapes`` says why).

Controls: ``skip_layer`` leaves that layer out of the LAST pass alone, one
of 192 layer visits: the smallest fault the check should catch;
``control_logprobs``' switches compute what a port with ONE thing wrong would
(``skip_pass`` t: a whole pass left out; ``sandwich`` false: no norm of a
sublayer's output; ``between`` false: no norm between passes; ``precision``:
every tensor the configuration's dtype holds rounded to "bfloat16" or
"float8_e4m3fn").

Tolerance: lib/reference.py's (the dense block's: median 0.02, root mean
square 0.06, worst 0.25), no ``ALLOWED_NATS`` here: it was measured to fit.
On one v5e at the cell's size (48 layers x 4 passes, int8 weights; my chip
runs, PR 48, call 6: the runner's served path, prefill then windows through
the pool, in the check's shape, 4 prompts of 64 tokens and 16 tokens each,
60 seeds; the controls on 12 of them; PERF.md section 6 has the calls):

    nat                                  median        root mean sq. worst token
    served, 60 seeds (mean 0.0086,
      0.0127, 0.033; sd 0.0013, 0.0014,
      0.0044)                            0.006-0.012   0.010-0.016   0.025-0.044
    this forward in bfloat16
      (``precision``; the stream too)    0.017-0.033   0.033-0.041   0.083-0.122
    a layer left out of the last pass:
      layer 47 | 23 | 0 (fails at 4 | 8
      | 9 of 12 seeds)                   0.014-0.033   0.022-0.042   0.053-0.097
    a whole pass left out (12 of 12)     0.082-0.137   0.112-0.159   0.28-0.49
    no norm between passes               0.44-0.74     0.65-0.89     1.5-2.6
    float8 activations                   0.50-0.86     0.70-0.91     1.6-2.4
    no norm of a sublayer's output       4.0-4.9       4.0-4.7       5.9-7.4

The limits leave the served path 1.7, 3.7 and 5.7 times of room, a whole
pass fails by four times the median and float8 by 25. ONE layer of the last
pass (1 of 192 visits, two sublayers at gains of a 45th) moves the median
to where the limit is and is caught at a third to three quarters of the
seeds: a root mean square of 0.019 would tell it from the served path at
every seed measured (0.0225 at least against 0.0162 at most) with 1.2 times
of room on either side, which one sound run in some dozens would not
survive, so it is not stated. At a prompt of 1,000 tokens the served path
reads 0.004 and a layer or a pass left out 0.015 to 0.017 (call 2, gains of
1): long contexts flatten what a layer adds, and the check's 64-token
prompts are where it sees.

Why the gains of the output norms are small (``attn_out_gain``,
``mlp_out_gain``: columns, which the weight law draws normal / sqrt(2048)):
with gains of 1, as a ``*_norm`` leaf is drawn, the same check read on 48
seeds median 0.001-0.038, root mean square 0.002-0.65, worst 0.006-2.6,
eight seeds of 48 outside the dense tolerance and two outside any that
float8 fails (call 5; with a bfloat16 stream besides, one prompt of one
benchmark run parted by 7 nats from its first token on, call 2): every
sublayer then adds a unit vector whatever it computed, all four passes
amplify a rounding alike, and the bfloat16 program is another function than
the float32 one at one seed in six.
"""

from __future__ import annotations

import functools
import math

from benchmark.lib.reference import (_head_fn, plain, rms_norm, rope,
                                     table_shape)
# The block kind this reference is of, as the program's reader states it: a
# program whose reader knows no ``ouro`` fails in run.py before anything is
# launched.
from dynamo_tpu.engine.config import OuroSpec  # noqa: F401


@functools.cache
def make_layer(nh: int, nkv: int, d: int, eps: float, theta: float, *,
               sandwich: bool = True, precision: str = "float32"):
    """``layer(x, layers, index)`` over ``params["layers"]``, jitted."""
    import jax
    import jax.numpy as jnp

    def low(a):
        """A tensor the configuration's dtype holds, at the values
        ``precision`` holds, by arithmetic XLA cannot drop
        (references/nemotron_h.py ``rounded`` has the why)."""
        if precision == "float32":
            return a
        kept = jnp.finfo(getattr(jnp, precision))
        out = jax.lax.reduce_precision(a, exponent_bits=8,
                                       mantissa_bits=kept.nmant)
        if kept.nexp == 8:
            return out
        tiny, top = float(kept.tiny), float(kept.max)
        step = tiny * 2.0 ** -kept.nmant
        return jnp.where(jnp.abs(a) < tiny, jnp.round(a / step) * step,
                         jnp.clip(out, -top, top))

    def out_norm(y, weight):
        return low(rms_norm(y, weight, eps)) if sandwich else y

    def layer(x, layers, index):
        lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, index, 0, keepdims=False), layers)
        s = x.shape[0]
        h = low(rms_norm(x, lp["input_norm"], eps))
        q = low(rope(low(h @ plain(lp["wq"])).reshape(s, nh, d), theta))
        k = low(rope(low(h @ plain(lp["wk"])).reshape(s, nkv, d), theta))
        v = low(h @ plain(lp["wv"])).reshape(s, nkv, d)
        k = jnp.repeat(k, nh // nkv, axis=1)
        v = jnp.repeat(v, nh // nkv, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d)
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf),
                               axis=-1)
        attn = low(jnp.einsum("hqk,khd->qhd", low(probs), v)
                   .reshape(s, nh * d))
        x = low(x + out_norm(low(attn @ plain(lp["wo"])),
                             lp["attn_out_gain"][:, 0]))
        h2 = low(rms_norm(x, lp["post_attn_norm"], eps))
        inner = low(jax.nn.silu(low(h2 @ plain(lp["w_gate"])))
                    * low(h2 @ plain(lp["w_up"])))
        return low(x + out_norm(low(inner @ plain(lp["w_down"])),
                                lp["mlp_out_gain"][:, 0]))

    return jax.jit(layer), low


def hidden_states(params, spec, tokens, skip_layer: int | None = None,
                  skip_pass: int | None = None, between: bool = True,
                  **switches):
    """The residual stream [S, H] after the last pass, ahead of the final
    norm: embedding, ``spec.loop_passes`` passes over the layers, the final
    norm between two passes. Under ``highest`` precision (the caller's)."""
    import jax.numpy as jnp
    eps = float(spec.rms_norm_eps)
    layer, low = make_layer(spec.num_heads, spec.num_kv_heads, spec.head_dim,
                            eps, float(spec.rope_theta), **switches)
    passes = [t for t in range(spec.loop_passes) if t != skip_pass]
    embed = params["embed"]
    rows = (embed.q[tokens] if hasattr(embed, "q") else embed[tokens])
    x = rows.astype(jnp.float32)
    if hasattr(embed, "s"):
        x = x * embed.s.astype(jnp.float32)[0]
    x = low(x)
    for t in passes:
        for index in range(spec.num_layers):
            if not (t == passes[-1] and index == skip_layer):
                x = layer(x, params["layers"], jnp.int32(index))
        if t != passes[-1] and between:
            x = low(rms_norm(x, params["final_norm"], eps))
    return x


def all_logprobs(params, spec, tokens, first: int = 0, **switches):
    """log-softmax [S - first, V] of the positions from ``first`` on: the
    final norm (the one after the last pass) and the head over
    ``hidden_states``."""
    import jax
    import numpy as np
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, spec, np.asarray(tokens, np.int32),
                          **switches)
        tied = bool(spec.tie_word_embeddings)
        table = params["embed"] if tied else params["lm_head"]
        vocab = table_shape(table)[0 if tied else 1]
        chunks = next(c for c in (8, 4, 2, 1) if vocab % c == 0)
        return _head_fn(float(spec.rms_norm_eps), tied, chunks)(
            x[first:], params["final_norm"], table)


def teacher_forced(params, spec, prompt: list[int], generated: list[int],
                   **switches) -> list[float]:
    """Logprob of each generated token under the plain forward of ``prompt
    + generated[:-1]``; only the positions that predict a generated token
    reach the head."""
    import jax.numpy as jnp
    import numpy as np
    logp = all_logprobs(params, spec, list(prompt) + list(generated[:-1]),
                        first=len(prompt) - 1, **switches)
    picked = logp[jnp.arange(len(generated)),
                  jnp.asarray(generated, jnp.int32)]
    return [float(v) for v in np.asarray(picked, np.float64)]


def reference_logprobs(params, spec, prompt: list[int],
                       generated: list[int], skip_layer: int | None = None
                       ) -> list[float]:
    """The logprob this forward gives each generated token; ``skip_layer``
    leaves that layer out of the LAST pass alone."""
    return teacher_forced(params, spec, prompt, generated,
                          skip_layer=skip_layer)


def control_logprobs(params, spec, prompt: list[int], generated: list[int],
                     **switches) -> list[float]:
    """``reference_logprobs`` with ONE thing wrong (long_prompt.py
    ``--control key=value``): ``skip_layer`` / ``skip_pass`` (a number),
    ``sandwich`` / ``between`` (false), ``precision`` (a dtype's name)."""
    for key in ("skip_layer", "skip_pass"):
        if key in switches:
            switches[key] = int(switches[key])
    return teacher_forced(params, spec, prompt, generated, **switches)
