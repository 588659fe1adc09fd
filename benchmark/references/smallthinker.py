"""Plain reference of the SmallThinker block (PowerInfer/SmallThinker-21BA3B-
Instruct): a router AHEAD of attention over 64 ReGLU experts, NoPE global
layers among RoPE sliding-window layers. ``jax.numpy`` only, float32,
``highest`` precision, over the parameters as stored (int8 leaves
dequantised: q * s); nothing of engine/model.py. Embedding, the loop over
layers, final norm and head are lib/reference.py ``teacher_forced``.

For layer l, input x [S, hidden] (the residual stream as it ENTERS the layer):
 1. r = x . W_r (no bias): the router reads x before ``input_layernorm``,
    AS BFLOAT16 HOLDS IT (see "The one rounding" below).
 2. h = RMSNorm(x; g1); q, k, v = h . Wq, h . Wk, h . Wv, no bias.
 3. rope_layout[l] == 1: rotate-half RoPE over the whole head on q and k;
    0: none (NoPE).
 4. causal attention, scale 1/sqrt(head_dim), softmax in float32;
    sliding_window_layout[l] == 1: query i sees key j iff i - W < j <= i;
    0: every j <= i.
 5. x1 = x + attn . Wo; h2 = RMSNorm(x1; g2).
 6. p = softmax(r) over all experts; S = the k largest; w_e = p_e / sum_S p
    (``norm_topk_prob``); y = sum_{e in S} w_e ((relu(h2 . Wg_e) * (h2 .
    Wu_e)) . Wd_e); x_out = x1 + y.
 7. (teacher_forced) final RMSNorm, untied head.
Every expert is computed for every token and the unchosen weighted by zero,
an expert and a head at a time (``lax.scan`` / ``lax.map``), so the same
code checks 80 tokens and 5,000 beside a serving engine.

Assumed, there being no network here to read the model's code (the
configuration file lists the same under ``assumed``): the router's input is
the layer's input (llama.cpp's ``llm_build_smallthinker`` applies
``ffn_gate_inp`` to ``inpL``; the HF layer keeps ``router_input =
hidden_states`` ahead of the norm); RoPE is the rotate-half form; the gate
activation is ReLU ("sparse ReGLU"); the catalog's ``described_as`` speaks
of "secondary" experts and the config has none, so there are none and no
shared expert; ``moe_primary_router_apply_softmax`` true means step 6's
softmax over all experts before the choice.

The one rounding (``router="bfloat16"``, the default). Step 6 is a CHOICE:
where the sixth and the seventh expert are a near tie, which one a token
gets is decided by the last bits of r. The configuration computes in
bfloat16, so the stream its router reads is a bfloat16 tensor, and the
choice the model makes is the one those 8 bits of mantissa give. A forward
that reads the router's input in float32 makes ANOTHER choice at such a
tie, and with random weights ties are common (the first layers' softmax over
64 is nearly flat: six experts weigh about a sixth each). So equation 1
rounds x to bfloat16 for the router's product alone; everything else,
the product itself included, stays float32. Measured (below): at seed
2147498339 the float32 router read 0.150 nat from the served path, all four
prompts moved together, and this one rounding reads 0.015; over 74 seeds it
takes the largest median from 0.150 to 0.058. Rounding the stream, q, k, v
and every product's input as well (``precision="bfloat16"``) reads the same
as the one rounding (0.013 to 0.054): what is left is the order of the
program's own sums, which no reference can follow.

``make_layer``'s keywords switch ONE equation each to what a careless port
would compute (``use_window``, ``use_nope``, ``router_reads_input``, ``relu``,
``renorm``), or compute in a lower precision (``precision``: every tensor
the configuration's dtype holds, that is the stream, q, k, v and what enters
each product, rounded to "bfloat16" or "float8_e4m3fn"; sums and softmax
stay float32); tests/test_smallthinker.py and the builder's chip runs use
them as controls.

ALLOWED_NATS, measured on one v5e at the cell's size (24 layers, int8
weights; the check's 4 prompts x 16 tokens after 64-token prompts; my chip
runs, PR 28, calls 7 to 11: 74 seeds in three server processes with the
weights swapped in place and the cell's own check in twelve runs, each seed
its own weights and words; against what was SERVED; nat, smallest to
largest):

                                   median        root mean sq.  worst token
    this reference, 86 seeds       0.012-0.058   0.027-0.115    0.095-0.575
      (85 of them                  0.012-0.051   0.027-0.106    0.095-0.515)
    its router in float32, 74      0.016-0.150   0.036-0.192    0.126-0.802
    computed in float8 (e4m3), 54  0.273-1.286   0.514-1.421    1.094-3.277
    LAST layer left out, 86        0.084-0.368   0.147-0.412    0.366-1.176
    float8 into the four products
      only (stream, q, k, v
      exact), 60                   0.067-0.198   0.124-0.281    0.289-0.879

and at 20 seeds each, one equation wrong: SiLU for ReLU 0.114-0.424 (rms
0.248-0.583), RoPE on every layer 0.200-0.459, top-k without renormalising
0.252-0.883, the router fed the normalised state 0.495-1.593; FIRST layer
left out 1.91-5.68 (calls 1 to 3).

MEDIAN 0.09 is 1.55 times the largest median a sound run read and a third
of the smallest the float8 forward read; RMS 0.15 is 1.3 times and 0.29;
WORST 0.9 is 1.57 times and 0.82. The float8 forward, the nearest precision
below the configuration's, fails all three at every seed. By one limit or
another (the rule is all three must hold) the skipped last layer fails at
all 86 seeds (its median alone passes 0.09 at 4 of them: its root mean
square is then 0.157 to 0.209), every wrong equation at all 20, and float8
into the four products alone at 57 of 60. The sound readings are three
times the dense block's (lib/reference.py: 0.005 to 0.010) because near
ties the program's own sums decide remain; the limits leave them 1.3 to 1.6
times of room, not the dense block's 2, and PERF.md section 6 says what
that costs.
"""

from __future__ import annotations

import functools
import math

from benchmark.lib.reference import plain, rms_norm, rope, teacher_forced

ALLOWED_NATS = {"median": 0.09, "rms": 0.15, "worst": 0.9}


@functools.cache
def make_layer(nh: int, nkv: int, d: int, eps: float, theta: float,
               top_k: int, window: int | None, window_layout: tuple,
               rope_layout: tuple, *, use_window: bool = True,
               use_nope: bool = True, router_reads_input: bool = True,
               relu: bool = True, renorm: bool = True,
               router: str = "bfloat16", precision: str = "float32"):
    import jax
    import jax.numpy as jnp

    def to(a, name):
        if name == "float32":
            return a
        return a.astype(getattr(jnp, name)).astype(jnp.float32)

    def low(a):
        """A tensor the configuration's dtype holds, as ``precision`` does."""
        return to(a, precision)

    def layer(x, layers, index):
        lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, index, 0, keepdims=False), layers)
        s = x.shape[0]
        x = low(x)
        r = to(x, router) @ plain(lp["moe_gate"])                # 1
        h = low(rms_norm(x, lp["input_norm"], eps))              # 2
        q = (h @ plain(lp["wq"])).reshape(s, nh, d)
        k = (h @ plain(lp["wk"])).reshape(s, nkv, d)
        v = (h @ plain(lp["wv"])).reshape(s, nkv, d)
        roped = jnp.asarray(rope_layout, bool)[index]            # 3
        if not use_nope:
            roped = jnp.asarray(True)
        q = jnp.where(roped, rope(q, theta), q)
        k = jnp.where(roped, rope(k, theta), k)
        q, k, v = low(q), low(k), low(v)
        k = jnp.repeat(k, nh // nkv, axis=1)
        v = jnp.repeat(v, nh // nkv, axis=1)
        i = jnp.arange(s)[:, None]                               # 4
        j = jnp.arange(s)[None, :]
        seen = j <= i
        if use_window and window:
            windowed = jnp.asarray(window_layout, bool)[index]
            seen = seen & (~windowed | (i - window < j))

        def one_head(qkv):
            # A head at a time: [S, S] float32 scores fit beside a server
            # at 5,000 tokens, where [heads, S, S] would not.
            qh, kh, vh = qkv
            scores = jnp.where(seen, qh @ kh.T / math.sqrt(d), -jnp.inf)
            return jax.nn.softmax(scores, axis=-1) @ vh

        attn = jax.lax.map(one_head, tuple(
            a.transpose(1, 0, 2) for a in (q, k, v)))            # [nh, S, d]
        attn = attn.transpose(1, 0, 2).reshape(s, nh * d)
        x1 = x + low(attn) @ plain(lp["wo"])                     # 5
        x1 = low(x1)
        h2 = low(rms_norm(x1, lp["post_attn_norm"], eps))
        if not router_reads_input:
            r = h2 @ plain(lp["moe_gate"])
        p = jax.nn.softmax(r, axis=-1)                           # 6
        top_p, top_i = jax.lax.top_k(p, top_k)
        if renorm:
            top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        weight = jnp.zeros_like(p).at[jnp.arange(s)[:, None], top_i].set(
            top_p)                                               # [S, E]

        def one_expert(y, expert):
            # An expert at a time, every token, the unchosen weighted by
            # zero: one expert's float32 matrices are live, not 64.
            wg, wu, wd, w_e = expert
            gate = h2 @ plain(wg)
            act = jax.nn.relu(gate) if relu else jax.nn.silu(gate)
            return y + w_e[:, None] * (low(act * (h2 @ plain(wu)))
                                       @ plain(wd)), None

        y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x1), (
            lp["moe_w_gate"], lp["moe_w_up"], lp["moe_w_down"], weight.T))
        return x1 + y

    return jax.jit(layer)


def layer_of(spec, **switches):
    """``layer(x, layers, index)`` of ``spec`` (a ModelSpec as the
    program's ``from_hf_config`` reads the configuration)."""
    n = spec.num_layers
    return make_layer(
        spec.num_heads, spec.num_kv_heads, spec.head_dim,
        float(spec.rms_norm_eps), float(spec.rope_theta),
        spec.num_experts_per_tok, spec.sliding_window,
        tuple(spec.sliding_window_layout or (0,) * n),
        tuple(spec.rope_layout or (1,) * n), **switches)


def reference_logprobs(params, spec, prompt: list[int],
                       generated: list[int], skip_layer: int | None = None
                       ) -> list[float]:
    return teacher_forced(params, spec, prompt, generated, layer_of(spec),
                          skip_layer)


def control_logprobs(params, spec, prompt: list[int], generated: list[int],
                     **switches) -> list[float]:
    """``reference_logprobs`` with ``make_layer``'s switches: what a port
    with that one equation wrong would give."""
    return teacher_forced(params, spec, prompt, generated,
                          layer_of(spec, **switches))
