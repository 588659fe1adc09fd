"""Plain reference of the Cohere2-MoE block (CohereLabs/command-a-plus-05-2026,
``model_type`` ``cohere2_moe``) for ONE share of an expert-parallel
deployment: a parallel block over one LayerNorm, a sigmoid router as wide as
the deployment has experts, of which the parameters hold ``num_experts`` from
``first_expert`` on, shared experts averaged, interleaved RoPE on window
layers and none on full layers, a tied head. ``jax.numpy`` only, float32,
``highest`` precision, over the parameters as stored (int8 leaves
dequantised: q * s); nothing of engine/model.py. lib/reference.py's
``teacher_forced`` ends in an RMS final norm, so the loop over layers, the
final LayerNorm and the tied head are here; ``plain`` is that module's.

For layer l, input x [S, hidden] (what enters the layer):
 1. h = LN(x; w) = (x - mean(x)) / sqrt(var(x) + eps) * w: mean-centred, no
    bias, the layer's ONLY norm (``use_parallel_block``).
 2. q, k, v = h Wq, h Wk, h Wv: no bias, no qk-norm.
 3. ``layer_types[l]`` "sliding_attention": q and k rotated in interleaved
    pairs (2i, 2i + 1) at frequency theta ** (-2i / head_dim)
    (``rope_gptj``, ``rotary_pct`` 1), and query i sees key j iff
    i - window < j <= i. "full_attention": no rotation, every j <= i.
 4. a = softmax(q k^T / sqrt(head_dim)) v Wo, softmax in float32.
 5. s = sigmoid(h Wr) over ALL routed experts (the router's width); C = the
    k largest; g_e = s_e / sum over C of s (``norm_topk_prob``: over all k
    chosen, wherever they are held).
 6. E(h; W) = (silu(h Wg) * (h Wu)) Wd, the same width for routed and
    shared; routed = sum over e in C AND HELD HERE of g_e E(h; W_e): what
    the experts held elsewhere would add is left out, here as in the
    program; shared = mean over the shared experts j of E(h; S_j)
    (``shared_expert_combination_strategy`` "average").
 7. x_out = x + a + routed + shared.
 8. logits = LN(x_L; w_f) Emb^T (``tie_word_embeddings``, ``logit_scale`` 1),
    over the vocabulary rows held.
Every held expert is computed for every token and the unchosen weighted by
zero, an expert and a head at a time (``lax.scan`` / ``lax.map``), so the same
code checks 80 tokens and 5,000 beside a serving engine.

Assumed, there being no network here to read the model's code (the
configuration file lists the same under ``assumed``): an expert's width is
``intermediate_size`` (the catalog's own note) and a shared expert's is the
same; "average" is the mean of the shared experts' outputs, added to the
routed sum (the other reading, (routed + shared) / 2, is the control
``shared="halved"``); no selection bias and no routed scaling factor (the
config has neither key); ``first_k_dense_replace`` 0, so the
``prefix_dense_*`` keys act on no layer; window layers rotate and full layers
do not (``described_as``: "SWA; global NoPE", Cohere2's own pattern); the
vision tower is outside the language model's config and outside this file.

``make_layer``'s keywords switch ONE equation each to what a careless port
would compute: ``interleaved`` false (rotate-half pairs), ``sigmoid`` false
(softmax over the router's width), ``norm_over`` "held" (gates divided by
the sum over the chosen experts held HERE), ``shared`` "none" (left out) or
"halved" ((routed + shared) / 2), ``parallel`` false (the feed-forward reads
the norm of x + a), ``use_window`` false, ``use_nope`` false (full layers
rotated too); ``precision`` computes every tensor the configuration's dtype
holds in "bfloat16" or "float8_e4m3fn". ``parts`` returns step 7's three
terms apart, for the test that the shares add up to the uncut layer.
"halved" reads "average" as (routed + the shared experts' SUM) / 2.

ALLOWED_NATS, measured on one v5e at the cell's size (8 layers, int8
weights, 16 of 128 experts held; the check's 4 prompts x 16 tokens after
64-token prompts; my chip runs, PR 32, call 2: 18 seeds in one server
process with the weights swapped in place, each seed its own weights and
words, and the cell's own check in two runs of call 1; against what was
SERVED; nat, smallest to largest):

                                   median         root mean sq.  worst token
    this reference, 20 seeds       0.0014-0.0030  0.0035-0.0208  0.014-0.084
      (19 of them                  0.0014-0.0026  0.0035-0.0108  0.014-0.042)
    computed in bfloat16, 18       0.0015-0.0036  0.0023-0.0084  0.006-0.046
    computed in float8 (e4m3), 18  0.0252-0.0599  0.0406-0.0768  0.105-0.211
    LAST layer left out, 20        0.047-0.259    0.107-0.305    0.189-0.551
    FIRST layer left out, 20       1.20-1.83      1.23-1.76      1.71-2.55
    softmax for sigmoid            0.0087-0.0378  0.0148-0.0411  0.035-0.085
    RoPE on full layers too        0.0083-0.0295  0.0177-0.0340  0.044-0.084
    rotate-half RoPE               0.046-0.103    0.073-0.122    0.168-0.322
    shared experts left out        0.093-0.289    0.155-0.313    0.356-0.716
    (routed + shared) / 2          0.146-0.444    0.226-0.447    0.467-1.001
    gates normalised over the
      held experts alone           0.234-0.517    0.322-0.546    0.653-1.269
    sequential residual            0.281-0.642    0.375-0.696    0.700-1.323

The router in float32 (this file) and the router's input rounded to
bfloat16 read alike (0.0012-0.0032 against 0.0014-0.0030): a choice of 8
among 128 of which an eighth is held, over a NORMALISED input, beside four
shared experts no choice touches, leaves no near tie that matters, so this
reference rounds nothing (references/smallthinker.py has to). The sound
readings are UNDER the dense block's (lib/reference.py: 0.005 to 0.010).

MEDIAN 0.008 is 2.7 times the largest median a sound run read and a third
of the smallest the float8 forward read; RMS 0.035 is 1.7 times and 0.86;
WORST 0.17 is twice the largest sound reading (one seed's one token at
0.084, the other nineteen under 0.042) and does not separate float8 (0.105
to 0.211): the worst token catches a fault in a few tokens, which moves
them by whole nats. By the median and by the root mean square the float8
forward, the nearest precision below the configuration's, fails at all 18
seeds, as does every control above (the rule is all three must hold);
softmax for sigmoid and RoPE on full layers are the closest, 1.04 to 1.09
times the median limit at their smallest. At 5,000 tokens
(benchmark/long_prompt.py, call 2) the served path read 0.0009 | 0.0115 |
0.0455, ``use_window=false`` 0.071 | 0.084 | 0.107, float8 0.117 | 0.135 |
0.198, rotate-half 0.024 | 0.038 | 0.070; RoPE on the two full layers is
NOT told apart there (0.0020 | 0.0118 | 0.0450).
"""

from __future__ import annotations

import functools
import math

from benchmark.lib.reference import plain

ALLOWED_NATS = {"median": 0.008, "rms": 0.035, "worst": 0.17}


def layer_norm(x, scale, eps):
    import jax.numpy as jnp
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale.astype(jnp.float32)


def rope(x, theta, interleaved: bool = True):
    """x [S, heads, D] at positions 0..S-1: frequency i turns the pair
    (2i, 2i + 1), or (i, i + D/2) in the rotate-half form."""
    import jax.numpy as jnp
    s, _, d = x.shape
    half = d // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if interleaved:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return out.reshape(x.shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.cache
def make_layer(nh: int, nkv: int, d: int, eps: float, theta: float,
               top_k: int, window: int | None, window_layout: tuple,
               first_expert: int, held: int, *, interleaved: bool = True,
               sigmoid: bool = True, norm_over: str = "chosen",
               shared: str = "mean", parallel: bool = True,
               use_window: bool = True, use_nope: bool = True,
               renorm: bool = True, precision: str = "float32",
               parts: bool = False):
    import jax
    import jax.numpy as jnp

    def to(a, name):
        if name == "float32":
            return a
        return a.astype(getattr(jnp, name)).astype(jnp.float32)

    def low(a):
        """A tensor the configuration's dtype holds, as ``precision`` does."""
        return to(a, precision)

    def experts(h, wg, wu, wd, weight):
        """sum over the stack's experts e of weight[:, e] * E(h; W_e), an
        expert at a time."""
        def one(y, expert):
            g, u, dn, w_e = expert
            ff = low(jax.nn.silu(h @ plain(g)) * (h @ plain(u)))
            return y + w_e[:, None] * (ff @ plain(dn)), None

        y, _ = jax.lax.scan(one, jnp.zeros_like(h), (wg, wu, wd, weight.T))
        return y

    def layer(x, layers, index):
        lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, index, 0, keepdims=False), layers)
        s = x.shape[0]
        x = low(x)
        h = low(layer_norm(x, lp["input_norm"], eps))            # 1
        q = (h @ plain(lp["wq"])).reshape(s, nh, d)              # 2
        k = (h @ plain(lp["wk"])).reshape(s, nkv, d)
        v = (h @ plain(lp["wv"])).reshape(s, nkv, d)
        windowed = jnp.asarray(window_layout, bool)[index]       # 3
        roped = windowed if use_nope else jnp.asarray(True)
        q = jnp.where(roped, rope(q, theta, interleaved), q)
        k = jnp.where(roped, rope(k, theta, interleaved), k)
        q, k, v = low(q), low(k), low(v)
        k = jnp.repeat(k, nh // nkv, axis=1)
        v = jnp.repeat(v, nh // nkv, axis=1)
        i = jnp.arange(s)[:, None]
        j = jnp.arange(s)[None, :]
        seen = j <= i
        if use_window and window:
            seen = seen & (~windowed | (i - window < j))

        def one_head(qkv):                                       # 4
            # A head at a time: [S, S] float32 scores fit beside a server
            # at 5,000 tokens, where [heads, S, S] would not.
            qh, kh, vh = qkv
            scores = jnp.where(seen, qh @ kh.T / math.sqrt(d), -jnp.inf)
            return jax.nn.softmax(scores, axis=-1) @ vh

        attn = jax.lax.map(one_head, tuple(
            a.transpose(1, 0, 2) for a in (q, k, v)))            # [nh, S, d]
        attn = attn.transpose(1, 0, 2).reshape(s, nh * d)
        a = low(attn) @ plain(lp["wo"])
        if not parallel:    # the feed-forward after attention, same weight
            h = low(layer_norm(low(x + a), lp["input_norm"], eps))
        r = h @ plain(lp["moe_gate"])                            # 5
        score = jax.nn.sigmoid(r) if sigmoid else jax.nn.softmax(r, axis=-1)
        top_s, top_i = jax.lax.top_k(score, top_k)
        local = top_i - first_expert
        here = (local >= 0) & (local < held)
        if renorm:
            over = top_s * here if norm_over == "held" else top_s
            top_s = top_s / jnp.maximum(
                jnp.sum(over, axis=-1, keepdims=True), 1e-30)
        weight = jnp.zeros((s, held), jnp.float32).at[
            jnp.arange(s)[:, None], jnp.where(here, local, held)].set(
            top_s, mode="drop")                                  # [S, held]
        routed = experts(h, lp["moe_w_gate"], lp["moe_w_up"],    # 6
                         lp["moe_w_down"], weight)
        n_shared = lp["shared_w_gate"].q.shape[0] if hasattr(
            lp["shared_w_gate"], "q") else lp["shared_w_gate"].shape[0]
        both = experts(h, lp["shared_w_gate"], lp["shared_w_up"],
                       lp["shared_w_down"],
                       jnp.full((s, n_shared), 1.0 / n_shared, jnp.float32))
        if shared == "none":
            both = jnp.zeros_like(both)
        if shared == "halved":
            routed, both = routed / 2, both * n_shared / 2
        if parts:
            return {"attention": a, "routed": routed, "shared": both}
        return x + a + routed + both                             # 7

    return jax.jit(layer)


def layer_of(spec, **switches):
    """``layer(x, layers, index)`` of ``spec`` (a ModelSpec as the
    program's ``from_hf_config`` reads the configuration): its share is
    experts ``first_expert`` to ``first_expert + num_experts - 1`` of a
    router ``router_width`` wide, and the parameters hold those."""
    return make_layer(
        spec.num_heads, spec.num_kv_heads, spec.head_dim,
        float(spec.rms_norm_eps), float(spec.rope_theta),
        spec.num_experts_per_tok, spec.sliding_window,
        tuple(spec.sliding_window_layout or (0,) * spec.num_layers),
        spec.first_expert, spec.num_experts, **switches)


@functools.cache
def _head_fn(eps: float, chunks: int):
    import jax
    import jax.numpy as jnp

    def head(x, final_norm, table):
        h = layer_norm(x, final_norm, eps)                       # 8
        rows = (table.q if hasattr(table, "q") else table).shape[0]
        width = rows // chunks

        def logits_of(c):
            # One slice of the vocabulary at a time: its float32 copy.
            part = jax.tree.map(lambda a: jax.lax.dynamic_slice_in_dim(
                a, c * width, width, 0) if a.shape[0] != 1 else a, table)
            return h @ plain(part).T

        out = jax.lax.map(logits_of, jnp.arange(chunks))         # [C, S, w]
        return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], -1)

    return jax.jit(head)


def hidden_states(params, spec, tokens, layer, skip_layer=None):
    """The stream after the last layer for ``tokens`` [S]: the embedding's
    rows, then ``layer`` for each layer but ``skip_layer``."""
    import jax.numpy as jnp
    import numpy as np
    tokens = np.asarray(tokens, np.int32)
    embed = params["embed"]
    x = (embed.q[tokens] if hasattr(embed, "q") else embed[tokens]).astype(
        jnp.float32)
    if hasattr(embed, "s"):
        x = x * embed.s.astype(jnp.float32)[0]
    for index in range(spec.num_layers):
        if index != skip_layer:
            x = layer(x, params["layers"], jnp.int32(index))
    return x


def logits_at(params, spec, x):
    """Final LayerNorm and tied head over the rows of ``x``: float32
    logits over the vocabulary rows the parameters hold."""
    table = params["embed"]
    rows = (table.q if hasattr(table, "q") else table).shape[0]
    chunks = next(c for c in (8, 4, 2, 1) if rows % c == 0)
    return _head_fn(float(spec.rms_norm_eps), chunks)(
        x, params["final_norm"], table)


def teacher_forced(params, spec, prompt, generated, layer,
                   skip_layer=None) -> list[float]:
    """lib/reference.py ``teacher_forced`` with this block's head: logprob
    of each generated token under the plain forward of
    ``prompt + generated[:-1]``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    n_prompt, n_gen = len(prompt), len(generated)
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, spec, list(prompt) + list(generated[:-1]),
                          layer, skip_layer)
        # Only the positions that predict a generated token reach the head.
        logp = jax.nn.log_softmax(logits_at(
            params, spec, x[n_prompt - 1:n_prompt - 1 + n_gen]), axis=-1)
        picked = logp[jnp.arange(n_gen), jnp.asarray(generated, jnp.int32)]
    return [float(v) for v in np.asarray(picked, np.float64)]


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
