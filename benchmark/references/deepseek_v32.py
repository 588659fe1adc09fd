"""Plain reference of the DeepSeek-V3.2 block (deepseek-ai/DeepSeek-V3.2-Exp,
``model_type`` ``deepseek_v32``) for ONE share of an expert-parallel
deployment: latent attention in the EXPANDED form, a learned indexer that
keeps ``index_topk`` keys a query by a true ``lax.top_k`` over float32 index
scores, leading dense layers, a grouped sigmoid router with a selection bias
as wide as the deployment has experts, of which the parameters hold
``num_experts`` from ``first_expert`` on, one shared expert, an untied head.
``jax.numpy`` only, float32, ``highest`` precision, over the parameters as
stored (int8 leaves dequantised: q * s); nothing of engine/model.py, no cache,
no absorbed product; its own loop over the dense prefix's leaves
(``dense_<name>``) and the expert layers'. ``plain`` is lib/reference.py's.

RMS(x; w) = x / sqrt(mean(x^2) + eps) * w, eps ``rms_norm_eps`` (1e-6).
Layer l, input x [S, hidden]:
 1. h = RMS(x; w_in); cq = RMS(h Wq_a; w_q) [q_lora_rank];
    q = cq Wq_b -> heads x (nope | rope).
 2. ckv = h Wkv_a -> [kv_lora_rank | rope]; c = RMS(ckv[:rank]; w_kv);
    kr = RoPE(ckv[rank:]): ONE rope key a token, shared by every head.
    kn_h = c Wk_b[h], v_h = c Wv_b[h] (the two halves of the checkpoint's
    kv_b_proj, which this repository stores as two leaves).
 3. qr_h = RoPE(q_h[nope:]). RoPE here turns INTERLEAVED pairs (2i, 2i + 1)
    at YaRN's frequencies: f_i = theta^(-2i/d); d(r) = d ln(original / (2 pi
    r)) / (2 ln theta); lo = floor(d(beta_fast)), hi = ceil(d(beta_slow));
    ramp_i = clip((i - lo) / (hi - lo), 0, 1); f'_i = f_i (1 - ramp_i) +
    f_i / factor * ramp_i; cos and sin unscaled (mscale = mscale_all_dim).
 4. The indexer: qI = cq WIq_b -> index heads x index_head_dim, the FIRST
    rope dims of each head turned in ROTATE-HALF pairs (i, i + rope/2) at
    the same frequencies; kI = LayerNorm(h WIk; g, b) (WITH bias, eps 1e-6),
    its first rope dims turned likewise; wI = (h WIw) * heads^-0.5 *
    index_head_dim^-0.5; I[t,s] = sum_j wI[t,j] relu(qI[t,j] . kI[s]) for
    s <= t; S_t = the min(index_topk, t + 1) positions of largest I[t,.].
 5. score_h[t,s] = (q_h[:nope] . kn_h[s] + qr_h . kr[s]) * (nope + rope)^-0.5
    * m^2, m = 0.1 mscale_all_dim ln(factor) + 1; a_h = softmax over s in
    S_t (score_h[t,.]) @ v_h; attn = concat_h(a_h) Wo.
 6. x1 = x + attn; h2 = RMS(x1; w_post).
 7. A leading dense layer (l < first_k_dense): x' = x1 + E(h2; W), E(h; W) =
    (silu(h Wg) * (h Wu)) Wd at ``intermediate_size``.
    An expert layer: s = sigmoid(h2 Wr) over ALL routed experts, float32;
    z_e = s_e + b_e (the selection bias: for the CHOICE alone); n_group runs
    of equal length, a group's score the sum of its 2 largest z, the
    topk_group best groups kept; C = the k largest z among their experts;
    g_e = s_e / sum over C of s * routed_scaling_factor (``norm_topk_prob``:
    over all k chosen, wherever they are held);
    x' = x1 + sum over e in C AND HELD HERE of g_e E(h2; W_e) + E(h2; W_shared)
    at ``moe_intermediate_size``: what the experts held elsewhere would add is
    left out, here as in the program.
 8. logits = RMS(x_L; w_f) W_head, over the vocabulary columns held.

Assumed, there being no network here to read the model's code (the
configuration file lists the same under ``assumed``): YaRN and m^2 apply at
every length (the HF reading); the published indexer stores kI in FP8 after a
Hadamard rotation of qI and kI, which is orthogonal and leaves every qI . kI
as it was, so it is left out; groups that were not kept are out of the choice
(the HF code fills their z with 0, which decides nothing while 8 of the kept
groups' 128 experts have z > 0); the multi-token-prediction module is a
draft and is not part of the model's own logits.

``make_layer``'s keywords switch ONE equation each to what a careless port
would compute: ``select`` false (every key s <= t), ``index_rope`` false (no
rotation in the indexer), ``groups`` false (the k largest z of all experts),
``bias`` false (z = s), ``scaling`` (1: no routed scaling factor), ``yarn``
false (plain frequencies; the scale keeps m^2), ``scale`` 128 (``128^-0.5``
without m^2), ``kv_norm`` false (c = ckv[:rank]); ``precision`` computes every
tensor the configuration's dtype holds in "bfloat16" or "float8_e4m3fn".
``parts`` returns step 7's terms apart, for the test that the shares add up
to the uncut layer.

ALLOWED_NATS, measured on one v5e at the cell's size (9 layers, int8 weights,
16 of 256 experts held; the check's 4 prompts x 16 tokens after 64-token
prompts; my chip runs, PR 34, call 3: 14 seeds in one server process with the
weights swapped in place, each seed its own weights and words; against what
was SERVED; nat, smallest to largest over the seeds):

                                   median         root mean sq.  worst token
    this reference, 14 seeds       0.021-0.081    0.053-0.217    0.160-1.650
      (13 of them                  0.021-0.040    0.053-0.112    0.160-0.702)
    computed in bfloat16           0.028-0.063    0.050-0.221    0.178-1.676
    computed in float8 (e4m3)      0.517-0.716    0.678-0.944    1.405-2.608
    LAST layer left out            0.259-0.431    0.416-0.594    0.971-2.032
    FIRST (dense) layer left out   3.68-4.30      3.83-4.32      5.63-7.34
    scale=128 (no m^2)             0.958-1.243    1.138-1.387    2.158-3.085
    kv_norm=false                  0.184-0.378    0.321-0.471    0.689-1.896
    bias=false                     0.138-0.236    0.263-0.379    0.628-1.303
    yarn=false                     0.119-0.225    0.227-0.413    0.589-2.061
    groups=false                   0.095-0.153    0.141-0.316    0.345-1.728
    scaling=1                      0.076-0.187    0.145-0.295    0.388-1.817
    select=false, index_rope=false as this reference: 80 keys are under 2,048
      and nothing is chosen (what 5,000 tokens are for, below)

The sound readings are ten times the Cohere2-MoE block's (0.0014 to 0.0030)
and SmallThinker's size (0.012 to 0.058). Where it comes from, measured at the
published widths on the CPU with one dense and one expert layer (PR 34): the
low-rank pairs round a query three times and a key three times where a plain
projection rounds once (h 0.17 %, cq 0.33 %, q and k 0.41 % off the float32
values), the softmax is 1.87 times sharper than 1/sqrt(d) (m^2), so attention
comes out 0.84 % off; on ONE input the expert layer's own arithmetic is 0.47 %
off and its choice never differs, but on inputs 0.84 % apart its output is
4.3 % off: the choice of 8 of 256 in 4 of 8 groups flips where the inputs
differ, a flipped expert carries a gate of 2.5 / 8 beside ONE shared expert,
and a flipped GROUP moves every held expert at once (experts 0 to 15 are half
of group 0). A router made decisive by scaling its weights saturates the
sigmoid and ties instead (measured: no change). Rounding the router's input
in the reference, SmallThinker's cure, does nothing here: the flips come from
what reaches the router, not from its own product.

MEDIAN 0.16 is twice the largest median a sound run read (four times the
other thirteen) and under a third of the smallest the float8 forward read;
RMS 0.45 is twice the largest sound reading and two thirds of float8's
smallest; WORST 3.5 is twice the largest sound reading (ONE token of one seed
at 1.65, the others under 0.71) and does not separate float8 (1.4 to 2.6): it
catches a fault in a few tokens, which moves them by whole nats. By the median
and by the root mean square the float8 forward, the nearest precision below
the configuration's, fails at all 14 seeds, as do a layer left out, scale=128
and kv_norm=false. **Four controls are NOT told apart at every seed by a check
of 64 tokens**: bias=false and yarn=false fail at 11 of the 14 seeds each,
scaling=1 at 2 and groups=false at none: each moves the median by 0.08 to
0.24, which is this block's own noise over 64 tokens (a limit under the
noisiest sound seed's 0.081 would refuse sound runs). The equations they
switch are held by the tests on the CPU instead (tests/test_deepseek_v32.py:
each control fails the toy's tolerance, the grouped router against a
written-out loop, YaRN against its formula), and a check over more tokens
would tell them apart here (PERF.md section 7).

At 5,000 tokens (2,048 of up to 5,016 keys kept; ONE prompt, 16 tokens;
benchmark/long_prompt.py with its controls on one seed and the same sweep on
five more, call 4; benchmark/selection_check.py on four more, calls 10 and
12; median | root mean square | worst, smallest to largest):

    this reference, 10 seeds       0.179-0.403    0.359-0.656    0.767-2.085
    computed in bfloat16, 5        0.201-0.374    0.323-0.538    0.691-1.843
    select=false (every key), 10   2.304-3.239    2.499-3.429    3.598-5.822
    index_rope=false, 6            2.312-3.613    2.568-3.317    4.129-4.741
    computed in float8, 10         1.301-2.297    1.548-2.099    2.412-3.686
    yarn=false 2.885 | 3.169 | 5.299, scale=128 2.275 | 2.238 | 3.541,
    kv_norm=false 0.746 | 0.997 | 2.327, bias=false 0.517 | 0.601 | 1.045,
    scaling=1 0.374 | 0.414 | 0.730, groups=false 0.273 | 0.367 | 1.012 (one
    seed each)

**Which keys differ, and what they cost** (selection_check.py, calls 10 and
12: the sets ``engine.model.select_topk`` returned in the programs that
served, the prefill chunks over history and the decode window, read back a
layer and held to this reference's ``top_k``; four seeds, 2,952 + 15 queries
a layer). In the FIRST layer, whose input is the embedding's rows on both
sides, the served sets hold 0.169 to 0.171 % keys (chunks; 0.238 to 0.290 %
in the window's 15 queries) that this reference's do not, and every one lies
within 0.036 standard deviations of the query's scores of the 2,048th score
(median 0.003, 99th percentile 0.016): the served indexer, over bfloat16
states, queries and cached keys, chooses this reference's set but AT the
boundary, in the chunk over history and in the window alike; no key from a
wrong position or under a wrong mask is among them (such a key would lie
whole deviations off). From there the share grows a layer, 2.7-2.8 % in the
second to 10.4-10.9 % in the ninth (window: 4.4-5.4 to 17.9-23.4 %), the
median margin 0.06 to 0.23 deviations (window 0.31), the 99th percentile 0.5
to 1.3 (1.5), the largest 2.1 to 4.2: the streams have drifted apart by then
(the expert layers' flips above), and a token whose experts flipped upstream
leaves another index key. GIVEN the served sets in every layer (``keeps``),
this reference reads 0.035-0.060 | 0.093-0.203 | 0.258-0.560 from what was
served, where choosing for itself it read 0.179-0.403 | 0.447-0.656 |
0.796-1.629 at the same four seeds: the distance at 5,000 tokens is the
64-token check's (0.021-0.081 | 0.053-0.217) plus what the keys that swapped
sides carry; the arithmetic over the chosen keys is as near as over 80 keys.

ALLOWED_NATS_SELECTING, for a context in which queries choose (what
selection_check.py judges by; long_prompt.py has one set of limits a
reference, ALLOWED_NATS, under which its verdict at 5,000 tokens reads
``served_ok`` false: its controls are what it is run for there): MEDIAN 0.7 is
1.7 times the largest sound median of 10 seeds and 0.54 of the smallest the
float8 forward read (0.30 of select=false's); RMS 1.0 is 1.5 times the
largest sound reading and 0.65 of float8's smallest; WORST 3.5 as above (it
does not separate float8: 2.4 to 3.7). Under them the four seeds of calls 10
and 12 pass and select=false and float8 fail at all four; of the eight named controls
groups=false (0.273) and scaling=1 (0.374) are inside at 5,000 tokens as at
64. A path that stored its index keys in float8 (the published model's own
storage, behind a Hadamard rotation) would swap more keys still.
"""

from __future__ import annotations

import functools
import math

from benchmark.lib.reference import plain
# The block kind this reference is of, as the program's reader states it: a
# program that cannot state it fails HERE, where run.py finds what the
# configuration names, before anything is launched (a reader that knows no
# ``deepseek_v32`` would otherwise serve these widths as a dense model for
# minutes, and fail at the check).
from dynamo_tpu.engine.config import DeepseekV32Spec

ALLOWED_NATS = {"median": 0.16, "rms": 0.45, "worst": 3.5}
#: Where queries CHOOSE (a context past ``index_topk``): what
#: benchmark/selection_check.py judges by.
ALLOWED_NATS_SELECTING = {"median": 0.7, "rms": 1.0, "worst": 3.5}

DENSE_PREFIX = "dense_"


def rms_norm(x, scale, eps):
    import jax.numpy as jnp
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale.astype(jnp.float32)


def layer_norm_bias(x, scale, bias, eps=1e-6):
    import jax.numpy as jnp
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x / jnp.sqrt(var + eps) * scale.astype(jnp.float32)
            + bias.astype(jnp.float32))


def frequencies(dim: int, theta: float, yarn: tuple | None):
    """The dim // 2 rotation frequencies of step 3 (plain where ``yarn`` is
    None), float32."""
    import numpy as np
    i = np.arange(dim // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / dim)
    if yarn is None:
        return f.astype(np.float32)
    factor, original, beta_fast, beta_slow = yarn[:4]

    def d_of(r):
        return dim * math.log(original / (2 * math.pi * r)) \
            / (2 * math.log(theta))

    lo = max(math.floor(d_of(beta_fast)), 0)
    hi = min(math.ceil(d_of(beta_slow)), dim - 1)
    ramp = np.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return (f * (1 - ramp) + f / factor * ramp).astype(np.float32)


def rope(x, freqs, interleaved: bool):
    """x [S, heads, D] at positions 0..S-1, D = 2 len(freqs): frequency i
    turns the pair (2i, 2i + 1), or (i, i + D/2) in the rotate-half form."""
    import jax.numpy as jnp
    s = x.shape[0]
    half = x.shape[-1] // 2
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(freqs)[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if interleaved:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return out.reshape(x.shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.cache
def make_layer(nh: int, nope: int, rdim: int, vdim: int, rank: int,
               idx_heads: int, idx_dim: int, topk: int, eps: float,
               theta: float, yarn: tuple | None, top_k: int, n_group: int,
               topk_group: int, factor: float, first_expert: int, held: int,
               dense_chunks: int = 1, *, select: bool = True, index_rope: bool = True,
               groups: bool = True, bias: bool = True,
               scaling: float | None = None, use_yarn: bool = True,
               scale: float | None = None, kv_norm: bool = True,
               precision: str = "float32", parts: bool = False,
               tell: bool = False):
    import jax
    import jax.numpy as jnp

    freqs = frequencies(rdim, theta, yarn if use_yarn else None)
    m = 1.0
    if yarn is not None and yarn[0] > 1:
        m = 0.1 * yarn[4] * math.log(yarn[0]) + 1.0
    sm_scale = (nope + rdim) ** -0.5 * m * m if scale is None \
        else float(scale) ** -0.5
    gate_scale = factor if scaling is None else float(scaling)

    def to(a, name):
        if name == "float32":
            return a
        return a.astype(getattr(jnp, name)).astype(jnp.float32)

    def low(a):
        """A tensor the configuration's dtype holds, as ``precision`` does."""
        return to(a, precision)

    def ffn(h, wg, wu, wd):
        return low(jax.nn.silu(h @ plain(wg)) * (h @ plain(wu))) @ plain(wd)

    def experts(h, wg, wu, wd, weight):
        """sum over the stack's experts e of weight[:, e] * E(h; W_e), an
        expert at a time."""
        def one(y, expert):
            g, u, dn, w_e = expert
            return y + w_e[:, None] * ffn(h, g, u, dn), None

        y, _ = jax.lax.scan(one, jnp.zeros_like(h), (wg, wu, wd, weight.T))
        return y

    def attention(x, lp, keep=None):
        s = x.shape[0]
        h = low(rms_norm(x, lp["input_norm"], eps))              # 1
        cq = low(rms_norm(h @ plain(lp["wq_a"]), lp["q_a_norm"], eps))
        q = (cq @ plain(lp["wq_b"])).reshape(s, nh, nope + rdim)
        ckv = h @ plain(lp["wkv_a"])                             # 2
        c = ckv[:, :rank]
        if kv_norm:
            c = rms_norm(c, lp["kv_a_norm"], eps)
        c = low(c)
        kr = low(rope(ckv[:, None, rank:], freqs, True)[:, 0])
        kn = low(c @ plain(lp["wk_b"])).reshape(s, nh, nope)
        v = low(c @ plain(lp["wv_b"])).reshape(s, nh, vdim)
        qn = low(q[..., :nope])
        qr = low(rope(q[..., nope:], freqs, True))               # 3
        i = jnp.arange(s)[:, None]
        j = jnp.arange(s)[None, :]
        seen = j <= i
        index = None
        if select and s > topk:                                  # 4
            qi = (cq @ plain(lp["index_wq_b"])).reshape(s, idx_heads,
                                                        idx_dim)
            ki = layer_norm_bias(h @ plain(lp["index_wk"]),
                                 lp["index_k_norm"],
                                 lp["index_k_bias"][:, 0])[:, None, :]
            if index_rope:
                qi = jnp.concatenate(
                    [rope(qi[..., :rdim], freqs, False), qi[..., rdim:]], -1)
                ki = jnp.concatenate(
                    [rope(ki[..., :rdim], freqs, False), ki[..., rdim:]], -1)
            qi, ki = low(qi), low(ki[:, 0])
            wi = (h @ lp["index_w"].astype(jnp.float32)) \
                * (idx_heads * idx_dim) ** -0.5

            def one_index_head(acc, head):
                # A head at a time: [S, S] float32 fits at 5,000 tokens.
                q_j, w_j = head
                return acc + w_j[:, None] * jnp.maximum(q_j @ ki.T, 0.0), None

            index, _ = jax.lax.scan(
                one_index_head, jnp.zeros((s, s), jnp.float32),
                (qi.transpose(1, 0, 2), wi.T))
            index = jnp.where(seen, index, -jnp.inf)
            if keep is None:
                _, chosen = jax.lax.top_k(index, topk)
                keep = jnp.zeros((s, s), bool).at[i, chosen].set(True)
        if keep is not None:
            seen = seen & keep

        def one_head(qkv):                                       # 5
            qn_h, qr_h, kn_h, v_h = qkv
            scores = (qn_h @ kn_h.T + qr_h @ kr.T) * sm_scale
            scores = jnp.where(seen, scores, -jnp.inf)
            return jax.nn.softmax(scores, axis=-1) @ v_h

        attn = jax.lax.map(one_head, tuple(
            a.transpose(1, 0, 2) for a in (qn, qr, kn, v)))      # [nh, S, v]
        attn = attn.transpose(1, 0, 2).reshape(s, nh * vdim)
        return low(attn) @ plain(lp["wo"]), index

    def routed(h2, lp):
        s = h2.shape[0]
        score = jax.nn.sigmoid(h2 @ lp["moe_gate"].astype(jnp.float32))
        z = score
        if bias and "moe_bias" in lp:
            z = z + lp["moe_bias"][:, 0].astype(jnp.float32)
        if groups and n_group > 1:
            per = z.shape[-1] // n_group
            grouped = z.reshape(s, n_group, per)
            best = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
            _, kept = jax.lax.top_k(best, topk_group)
            mask = jnp.zeros((s, n_group), bool).at[
                jnp.arange(s)[:, None], kept].set(True)
            z = jnp.where(jnp.repeat(mask, per, axis=1), z, -jnp.inf)
        _, top_i = jax.lax.top_k(z, top_k)
        top_s = jnp.take_along_axis(score, top_i, axis=-1)
        top_s = top_s / jnp.sum(top_s, axis=-1, keepdims=True) * gate_scale
        local = top_i - first_expert
        here = (local >= 0) & (local < held)
        weight = jnp.zeros((s, held), jnp.float32).at[
            jnp.arange(s)[:, None], jnp.where(here, local, held)].set(
            top_s, mode="drop")                                  # [S, held]
        return experts(h2, lp["moe_w_gate"], lp["moe_w_up"],
                       lp["moe_w_down"], weight)

    def by_columns(leaf, n):
        """[h, i] (values, or values and their scales [1, i]) as n blocks
        of columns [n, h, i / n]."""
        return jax.tree.map(lambda a: jnp.moveaxis(
            a.reshape(a.shape[0], n, -1), 1, 0), leaf)

    def by_rows(leaf, n):
        """[i, h] as n blocks of rows [n, i / n, h]; a scale [1, h] is every
        block's."""
        if hasattr(leaf, "q"):
            return type(leaf)(
                q=leaf.q.reshape(n, -1, leaf.q.shape[-1]),
                s=jnp.broadcast_to(leaf.s, (n, *leaf.s.shape)))
        return leaf.reshape(n, -1, leaf.shape[-1])

    def layer(x, stack, index, keep=None):
        """``stack``: the leaves of the layers of ONE kind (the leading
        dense layers' under the names without their prefix, or the expert
        layers'), ``index`` the layer among them. The layer's leaves are
        cut out here, inside the compiled function: a copy of every layer
        at once would not fit beside a serving engine. ``keep`` [S, S] bool
        GIVES step 4's sets (query t attends s where keep[t, s] and s <= t)
        in place of the indexer's choice; with ``tell`` the layer returns
        (x', I) with I [S, S] the float32 index scores, -inf where s > t
        (None where nothing is chosen)."""
        lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, index, 0, keepdims=False), stack)
        x = low(x)
        a, told = attention(x, lp, keep)
        out = feed_forward(x, a, lp)
        return (out, told) if tell else out

    def feed_forward(x, a, lp):
        x1 = low(x + a)                                          # 6
        h2 = low(rms_norm(x1, lp["post_attn_norm"], eps))
        if "moe_gate" not in lp:                                 # 7
            # The same sum a block of the width at a time (E is a sum over
            # its hidden units): 18,432 columns in float32 are 0.5 GB a
            # matrix.
            n = dense_chunks
            return x1 + experts(
                h2, by_columns(lp["w_gate"], n), by_columns(lp["w_up"], n),
                by_rows(lp["w_down"], n),
                jnp.ones((x.shape[0], n), jnp.float32))
        chosen = routed(h2, lp)
        w = lp["shared_w_gate"]
        n_shared = (w.q if hasattr(w, "q") else w).shape[0]
        shared = experts(h2, w, lp["shared_w_up"], lp["shared_w_down"],
                         jnp.ones((x.shape[0], n_shared), jnp.float32))
        if parts:
            return {"attention": a, "routed": chosen, "shared": shared}
        return x1 + chosen + shared

    return jax.jit(layer)


def layer_of(spec, **switches):
    """``layer(x, stack, index)`` of ``spec`` (a ModelSpec as the program's
    ``from_hf_config`` reads the configuration): its share is experts
    ``first_expert`` to ``first_expert + num_experts - 1`` of a router
    ``router_width`` wide, and the parameters hold those."""
    if not isinstance(spec, DeepseekV32Spec):
        raise TypeError(f"{type(spec).__name__} is not the DeepSeek-V3.2 "
                        "block's spec")
    if "yarn" in switches:
        switches["use_yarn"] = switches.pop("yarn")
    for key in ("scaling", "scale"):
        if key in switches:
            switches[key] = float(switches[key])
    wide, narrow = spec.intermediate_size, spec.expert_size
    return make_layer(
        spec.num_heads, spec.qk_nope_head_dim, spec.qk_rope_head_dim,
        spec.v_head_dim, spec.kv_lora_rank, spec.index_n_heads,
        spec.index_head_dim, spec.index_topk, float(spec.rms_norm_eps),
        float(spec.rope_theta),
        tuple(spec.rope_yarn) if spec.rope_yarn else None,
        spec.num_experts_per_tok, spec.n_group, spec.topk_group,
        float(spec.routed_scaling_factor), spec.first_expert,
        spec.num_experts, wide // narrow if wide % narrow == 0 else 1,
        **switches)


def layers_of(params, spec):
    """(stack, index) a layer, in the model's order: the leading dense
    layers' leaves (``dense_<name>``, the prefix taken off), then the expert
    layers'; what ``layer(x, stack, index)`` takes."""
    stacked = params["layers"]
    first = {k[len(DENSE_PREFIX):]: v for k, v in stacked.items()
             if k.startswith(DENSE_PREFIX)}
    rest = {k: v for k, v in stacked.items()
            if not k.startswith(DENSE_PREFIX)}
    dense = spec.first_k_dense
    return ([(first, i) for i in range(dense)]
            + [(rest, i) for i in range(spec.num_layers - dense)])


def hidden_states(params, spec, tokens, layer, skip_layer=None, keeps=None,
                  tell=None):
    """The stream after the last layer for ``tokens`` [S]: the embedding's
    rows, then ``layer`` for each layer but ``skip_layer``. ``keeps``: a
    layer's GIVEN sets each ([S, S] bool, ``layer``'s ``keep``); ``tell``
    (layer number, I) is handed each layer's index scores (a ``layer`` made
    with ``tell``)."""
    import jax.numpy as jnp
    import numpy as np
    tokens = np.asarray(tokens, np.int32)
    embed = params["embed"]
    x = (embed.q[tokens] if hasattr(embed, "q") else embed[tokens]).astype(
        jnp.float32)
    if hasattr(embed, "s"):
        x = x * embed.s.astype(jnp.float32)[0]
    for index, (stack, at) in enumerate(layers_of(params, spec)):
        if index == skip_layer:
            continue
        x = layer(x, stack, jnp.int32(at),
                  None if keeps is None else keeps[index])
        if tell is not None:
            x, scores = x
            tell(index, scores)
    return x


@functools.cache
def _head_fn(eps: float, chunks: int):
    import jax
    import jax.numpy as jnp

    def head(x, final_norm, table):
        h = rms_norm(x, final_norm, eps)                         # 8
        cols = (table.q if hasattr(table, "q") else table).shape[1]
        width = cols // chunks

        def logits_of(c):
            # One slice of the vocabulary at a time: its float32 copy.
            part = jax.tree.map(lambda a: jax.lax.dynamic_slice_in_dim(
                a, c * width, width, 1), table)
            return h @ plain(part)

        out = jax.lax.map(logits_of, jnp.arange(chunks))         # [C, S, w]
        return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], -1)

    return jax.jit(head)


def logits_at(params, spec, x):
    """Final RMSNorm and the untied head over the rows of ``x``: float32
    logits over the vocabulary columns the parameters hold."""
    table = params["lm_head"]
    cols = (table.q if hasattr(table, "q") else table).shape[1]
    chunks = next(c for c in (8, 4, 2, 1) if cols % c == 0)
    return _head_fn(float(spec.rms_norm_eps), chunks)(
        x, params["final_norm"], table)


def teacher_forced(params, spec, prompt, generated, layer,
                   skip_layer=None, **given) -> list[float]:
    """lib/reference.py ``teacher_forced`` with this block's loop over its
    two kinds of layer: logprob of each generated token under the plain
    forward of ``prompt + generated[:-1]``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    n_prompt, n_gen = len(prompt), len(generated)
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, spec, list(prompt) + list(generated[:-1]),
                          layer, skip_layer, **given)
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


def selection_logprobs(params, spec, prompt: list[int], generated: list[int],
                       keeps=None, tell=None) -> list[float]:
    """``reference_logprobs`` with step 4 opened, for
    benchmark/selection_check.py: ``keeps`` hands a layer's sets in, ``tell``
    (layer number, I) reads its index scores (``hidden_states``)."""
    layer = layer_of(spec) if tell is None else layer_of(spec, tell=True)
    return teacher_forced(params, spec, prompt, generated, layer,
                          keeps=keeps, tell=tell)


def control_logprobs(params, spec, prompt: list[int], generated: list[int],
                     **switches) -> list[float]:
    """``reference_logprobs`` with ``make_layer``'s switches: what a port
    with that one equation wrong would give."""
    return teacher_forced(params, spec, prompt, generated,
                          layer_of(spec, **switches))
