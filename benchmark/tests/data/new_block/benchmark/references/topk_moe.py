"""Plain reference of a Mixtral-style block: the dense block's attention,
then a routed feed-forward. ``jax.numpy`` only, float32, ``highest``
precision, over the parameters as stored (int8 leaves dequantised: q * s);
nothing of engine/model.py. Per token: the router's logits (hidden x
experts), the ``num_experts_per_tok`` largest, a softmax over those alone,
and the chosen experts' SwiGLU outputs summed under those weights. Every
expert is computed for every token and the unchosen weighted by zero: plain,
and a test's sizes allow it.

ALLOWED_NATS is this fixture's, measured where the test runs: on the CPU
backend, at the toy sizes (configs/toy-moe.json ``rehearsal_model``: 2
layers of 4 experts, 2 a token), the program's bf16 forward
(model.prefill_forward, then decode_forward a token) against this reference
over 4 prompts x 16 greedy tokens, 12 seeds with int8 weights and the same
12 with bf16 (builder's CPU runs, PR 27; nats of a toy, no device number):

    nat                      median         root mean square   worst token
    program, bf16 path       0.0059-0.0170  0.010-0.256        0.027-2.04
    LAST layer left out      1.10-1.59      1.34-1.74          2.53-4.05

A routed block adds what the dense tolerance (lib/reference.py) was never
measured against: where two experts' router logits tie to within bfloat16's
rounding, the program and this reference choose different experts for that
token, and its logprob moves by whole nats (in 13 of the 24 runs one token
of the 64 moved by more than 0.4 nat, and the root mean square is that
token's alone). So the median carries the check: 0.05 is three times the
largest a sound run read and a twentieth of the smallest with a layer left
out. The root mean square, 0.6, is 2.3 times the largest sound reading and
0.45 of the smallest faulty one. The worst token cannot tell a flipped
expert from a fault (2.04 against 2.53), so its limit is left where only a
number that is no logprob fails it. A configuration served on the chip
reads its own three numbers there, a dozen seeds or more and the control
beside them, before its PR sets them.
"""

from __future__ import annotations

import functools
import math

from benchmark.lib.reference import plain, rms_norm, rope, teacher_forced

ALLOWED_NATS = {"median": 0.05, "rms": 0.6, "worst": 10.0}


@functools.cache
def _layer_fn(nh: int, nkv: int, d: int, eps: float, theta: float,
              top_k: int):
    import jax
    import jax.numpy as jnp

    def layer(x, layers, index):
        lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, index, 0, keepdims=False), layers)
        s = x.shape[0]
        h = rms_norm(x, lp["input_norm"], eps)
        q = rope((h @ plain(lp["wq"])).reshape(s, nh, d), theta)
        k = rope((h @ plain(lp["wk"])).reshape(s, nkv, d), theta)
        v = (h @ plain(lp["wv"])).reshape(s, nkv, d)
        k = jnp.repeat(k, nh // nkv, axis=1)
        v = jnp.repeat(v, nh // nkv, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d)
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], scores,
                           -jnp.inf)
        attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
        x = x + attn.reshape(s, nh * d) @ plain(lp["wo"])

        h2 = rms_norm(x, lp["post_attn_norm"], eps)
        router = h2 @ plain(lp["moe_gate"])                     # [S, E]
        top_v, top_i = jax.lax.top_k(router, top_k)
        weight = jnp.zeros_like(router).at[
            jnp.arange(s)[:, None], top_i].set(jax.nn.softmax(top_v, -1))
        gate = jnp.einsum("sh,ehi->esi", h2, plain(lp["moe_w_gate"]))
        up = jnp.einsum("sh,ehi->esi", h2, plain(lp["moe_w_up"]))
        out = jnp.einsum("esi,eih->esh", jax.nn.silu(gate) * up,
                         plain(lp["moe_w_down"]))
        return x + jnp.einsum("esh,se->sh", out, weight)

    return jax.jit(layer)


def reference_logprobs(params, spec, prompt: list[int],
                       generated: list[int], skip_layer: int | None = None
                       ) -> list[float]:
    layer = _layer_fn(spec.num_heads, spec.num_kv_heads, spec.head_dim,
                      float(spec.rms_norm_eps), float(spec.rope_theta),
                      spec.num_experts_per_tok)
    return teacher_forced(params, spec, prompt, generated, layer, skip_layer)
