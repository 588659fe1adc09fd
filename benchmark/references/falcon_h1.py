"""Plain reference of the Falcon-H1 block (tiiuae/Falcon-H1-34B-Instruct,
``model_type`` ``falcon_h1``): every layer a Mamba-2 mixer AND rotary
attention SIDE BY SIDE on one normed input, summed into one residual, a dense
SwiGLU behind them, every muP multiplier WHERE IT IS PUBLISHED (none folded
into a weight or a neighbour). The recurrence is a ``lax.scan`` over time (no
chunks, no cache, no carried state), the attention in the expanded form a
head at a time. ``jax.numpy`` only, float32, ``highest`` precision, over the
parameters as stored (int8 leaves dequantised: q * s); nothing of engine/
but the spec class's name. ``plain``, ``rms_norm`` and ``rope`` are
lib/reference.py's.

``x0 = embedding_multiplier E[token]`` (5.6569). Layer, hidden 5,120, with
``u = RMS(h; w_in, 1e-5)`` (RMS(x; w) = x / sqrt(mean(x^2) + eps) * w):

- **SSM branch** (32 heads x 128 = 4,096 inner, 2 groups, state 256, 4
  taps): ``p = (ssm_in_multiplier u) W_in`` (5,120 -> 4,096 | 5,120 | 32);
  ``[z | x | B | C | dt] = p * m``, m constant a segment (``ssm_multipliers``:
  0.35355, 0.25, 0.17678, 0.5, 0.35355); ``xBC_t = silu(b_c + sum_{j=0..3}
  w_c[j] * [x | B | C]_{t-3+j})`` (depthwise, causal, zeros before the
  sequence); ``dt = softplus(dt + dt_bias)`` a head, no clamp; ``A =
  -exp(A_log)``; head h of group g = h // 16, S [128, 256] float32: ``S_t =
  exp(dt A) S_{t-1} + dt x_t (x) B_t[g]``, ``y_t = S_t C_t[g] + D[h] x_t``;
  ``y = y * silu(z)`` (the gate BEFORE the norm), RMS-normalised within each
  of 2 groups of 2,048, times a weight of 4,096; ``out_s =
  ssm_out_multiplier (y W_out)`` (0.088388).
- **attention** (20 query heads over 4 KV heads of 128): ``q = (attention_in_
  multiplier u) W_q``, ``k = key_multiplier (u W_k)`` (0.011049), ``v = u
  W_v``; rotate-half RoPE on q and k over all 128 lanes, theta 1e11, plain
  frequencies; causal, scale 128^-0.5; ``out_a = attention_out_multiplier
  (attn W_o)`` (0.0375).
- ``h <- h + out_s + out_a``: ONE norm in, ONE sum out.
- ``v = RMS(h; w_ff, 1e-5)``; ``h <- h + mlp_multipliers[1] ((W_u v) *
  silu(mlp_multipliers[0] (W_g v))) W_d`` (0.17678, 0.011161; width 21,504).

``logits = lm_head_multiplier (RMS(h_L; w_f) W_head)`` (1/128), untied head
of 261,120 rows.

Departures and assumptions (the configuration's file lists the same under
``assumed``; the model's code is not on this machine): the split orders ``z |
xBC | dt`` and ``x | B | C``; the five ``ssm_multipliers`` by segment in that
order on the in-projection's OUTPUT and ``ssm_in_multiplier`` on its input;
``key_multiplier`` ahead of the rotation; the gate before the grouped norm;
no clamp on dt; ``D`` and ``dt_bias`` a head. The leaves lie as
engine/model.py says: every matrix [in, out] (int8 with a scale a column
under the configuration's ``quant``), the Mamba-2 leaves as
``_recurrent_shapes`` (W_in as ``ssm_w_in`` z | x | B | C, 9,216 columns, and
``ssm_w_dt``, 32; the taps [4, 5120]; a head's vectors [32, 1]).

**What the multipliers do to the check.** The benchmark's weight law draws a
leaf normal / sqrt(shape[-2]): a matrix [in, out] keeps its input's size,
and the model's multipliers are published for TRAINED weights of other
sizes. ``lm_head_multiplier`` 1/128 on a head of unit-size columns puts every
logit within +-0.04 of 0: every logprob is -12.47 +- 0.03 (uniform over
261,120 ids is -12.4728), and every distance here is some hundred times
smaller than under the other references; noise and signal shrink together,
and the served float32 logprobs still resolve them (a step of float32 at
12.5 is 1e-6). Every score ``q . k / sqrt(128)`` is ``key_multiplier``'s size
(0.011): attention is uniform to a hundredth, so **the check does NOT see
the rotation** (``rope=false`` reads what the served path reads, at every
seed and at 5,000 tokens; the table below), though it sees ``key_multiplier``
(scores of 1 where they were 0.011), the attention branch (``attn=false``)
and how the branches are wired (``parallel=false``). B . C is 0.02, so the
state's term ``S C`` is a tenth of ``D x``: ``recurrence=false`` is the
weakest control that is told, with ``mlp=false`` (``mlp_multipliers`` make
the feed-forward's output a thousandth of the stream a layer), each by all
three limits at every one of 12 seeds at the check's 64 tokens; **at 5,000
tokens ``mlp=false`` is NOT told** (the stream has grown, the feed-forward's
share has not). The rotation is held on the CPU instead, under weights of
the sizes the constants are published for (tests/test_falcon_h1.py: whole
prompts, chunks over cached pages, windows), and on the chip by
scripts/falcon_ref_seeds.py's ``wk=`` probe (K's projection drawn at that
size; a builder's run, not the benchmark's). No leaf is laid otherwise and
no multiplier changed to make a control show: a weight law a LEAF, stated
in the configuration's file, is a ``benchmark`` PR's (PERF.md section 7).

``make_layers``' keywords switch ONE equation each to what a careless port
would compute: ``parallel`` false (attention reads ``RMS(h + out_s)``: the
sequential group), ``ssm`` false (no ``out_s``), ``attn`` false (no
``out_a``), ``rope`` false, ``key_multiplier`` (1), ``branch_multipliers``
(1: both 0.0375 and 0.088388), ``ssm_multipliers`` (1: the five),
``mlp_multipliers`` (1: both), ``gate_before_norm`` false (the norm, then the
gate), ``conv`` false (the current input's tap alone), ``skip`` false (no ``D
x``), ``recurrence`` false (``y = D x``: no state), ``mlp`` false (no
feed-forward), ``state`` ("bfloat16": S rounded after every step);
``precision`` computes every tensor the
configuration's dtype holds in "bfloat16" or "float8_e4m3fn".

ALLOWED_NATS: the table and the choice are above the constant.
"""

from __future__ import annotations

import functools

from benchmark.lib.reference import plain, rms_norm, rope, table_shape
# The block kind this reference is of, as the program's reader states it: a
# program whose reader knows no ``falcon_h1`` fails in run.py before
# anything is launched.
from dynamo_tpu.engine.config import FalconH1Spec  # noqa: F401

#: Largest median, root mean square and worst absolute difference (nat) of the
#: served logprobs from this forward that pass. Measured on one v5e at the
#: cell's size (12 layers, every matrix int8 [in, out]; my chip runs, PR 54,
#: call 8: the check's shape through the runner, 4 x 16 tokens after 64-token
#: prompts, 12 seeds, and one prompt of 5,000 tokens; the cell's own check in
#: calls 8 to 10), nat x 1e-5, least to largest over the seeds:
#:                                   median       root mean sq.  worst token
#:   served, 12 seeds                2.9-5.3      4.7-6.9        11.6-17.3
#:   served, the cell's check, 13    2.7-5.3      4.6-6.9        11.2-19.1
#:   served, one prompt of 5,000     2.5          3.7            9.4
#: and what was served against this forward with ONE equation switched, the
#: same 12 seeds [and the prompt of 5,000: median | rms | worst]:
#:   recurrence=false                13.7-41.7    29.2-51.8      62-116    [19.6 | 23.2 | 51]
#:   mlp=false                       13.6-51.8    29.8-65.6      71-124    [5.9 | 9.9 | 27: INSIDE]
#:   precision=float8_e4m3fn         49.8-95.1    68.9-125       159-302   [35.6 | 59.4 | 121]
#:   key_multiplier=1                54.9-335     97.3-371       238-673   [122 | 122 | 135]
#:   gate_before_norm=false          57.2-150     88.9-152       214-418   [43.0 | 52.9 | 134]
#:   branch_multipliers=1            77.5-451     216-441        395-878   [1395 | 1387 | 1528]
#:   skip=false                      90.9-327     197-313        390-824   [85.4 | 101 | 216]
#:   ssm=false                       91.7-332     215-330        429-799   [109 | 120 | 231]
#:   conv=false                      91.8-258     162-276        372-812   [29.0 | 50.7 | 152]
#:   parallel=false                  132-477      215-569        473-1236  [380 | 379 | 456]
#:   attn=false                      3092-3890    3096-3683      4567-5459 [4376 | 4254 | 5233]
#:   ssm_multipliers=1               3095-4090    3148-4007      4518-6188 [4299 | 4166 | 5004]
#:   mlp_multipliers=1               3130-4021    3188-3996      4735-5687 [3304 | 3315 | 4570]
#:   rope=false                      2.7-4.8      4.4-7.1        10.3-16.6 [2.3 | 3.6 | 8.2]
#:   state=bfloat16                  2.9-5.2      4.7-6.9        11.6-17.3 [2.5 | 3.7 | 9.4]
#:   precision=bfloat16              3.8-5.4      6.0-7.3        13.4-22.3 [3.4 | 4.8 | 8.9]
#: MEDIAN 9e-5 is 1.7 times the largest the served path read and two thirds
#: of the smallest any control that is told read (``mlp=false`` 1.36e-4,
#: ``recurrence=false`` 1.37e-4; float8, the nearest precision below the
#: configuration's bfloat16 activations, 5.0e-4 at its smallest). RMS 1.4e-4
#: is 2.0 times the largest sound reading and under half of the smallest
#: control's (2.9e-4); WORST 3.5e-4 is 1.8 times the largest sound token and
#: 0.56 of the smallest control's (6.2e-4). Thirteen controls fail by ALL
#: three at every one of the 12 seeds. THREE are not told at any seed:
#: **the rotation** (the docstring says why, and what holds it instead), a
#: bfloat16 STATE (under this law a state forgets in a few tokens, A about
#: -1 and dt about 0.7, as the other recurrent cells found; its type is
#: asserted: tests/test_falcon_h1.py, ``dtype`` of
#: ``dynamo_tpu_perf_ssm_state_info``) and the forward in bfloat16 (what is
#: served computes in it). ISSUE 54 asked that every control fail at every
#: seed: ``rope=false`` does not, and the limits were NOT moved for it (no
#: limit can part two readings that are alike). At 5,000 tokens ``mlp=false``
#: falls inside too; every other told control stands outside there.
ALLOWED_NATS = {"median": 9e-5, "rms": 1.4e-4, "worst": 3.5e-4}

BOOLS = ("parallel", "ssm", "attn", "rope", "gate_before_norm", "conv",
         "skip", "recurrence", "mlp")
FLOATS = ("key_multiplier", "branch_multipliers", "ssm_multipliers",
          "mlp_multipliers")


def make_layers(heads: int, head_dim: int, groups: int, state_n: int,
                taps_n: int, nh: int, nkv: int, d: int, eps: float,
                theta: float, key_mult: float, attn_in: float,
                attn_out: float, ssm_in: float, ssm_segments: tuple,
                ssm_out: float, mlp: tuple, *, parallel: bool = True,
                ssm: bool = True, attn: bool = True, rope_on: bool = True,
                key_multiplier: float | None = None,
                branch_multipliers: float | None = None,
                ssm_multipliers: float | None = None,
                mlp_multipliers: float | None = None,
                gate_before_norm: bool = True, conv: bool = True,
                skip: bool = True, recurrence: bool = True, mlp_on: bool = True,
                state: str = "float32", precision: str = "float32"):
    """``layer(x, layers, index)`` over ``params["layers"]``: layer ``index``
    is row ``index`` of every stack but the norms', whose rows 2 index and
    2 index + 1 are the layer's two."""
    import jax
    import jax.numpy as jnp

    inner = heads * head_dim
    bc = groups * state_n
    if key_multiplier is not None:
        key_mult = float(key_multiplier)
    if branch_multipliers is not None:
        attn_out = ssm_out = float(branch_multipliers)
    if ssm_multipliers is not None:
        ssm_segments = (float(ssm_multipliers),) * 5
    if mlp_multipliers is not None:
        mlp = (float(mlp_multipliers),) * 2

    def rounded(a, dtype: str):
        """``a`` (float32) at the values ``dtype`` holds, by arithmetic XLA
        cannot drop (references/nemotron_h.py ``rounded`` says why)."""
        if dtype == "float32":
            return a
        kept = jnp.finfo(getattr(jnp, dtype))
        out = jax.lax.reduce_precision(a, exponent_bits=8,
                                       mantissa_bits=kept.nmant)
        if kept.nexp == 8:
            return out
        tiny, top = float(kept.tiny), float(kept.max)
        step = tiny * 2.0 ** -kept.nmant
        return jnp.where(jnp.abs(a) < tiny, jnp.round(a / step) * step,
                         jnp.clip(out, -top, top))

    def low(a):
        """A tensor the configuration's dtype holds, as ``precision`` does."""
        return rounded(a, precision)

    def vec(leaf):      # a head's or a channel's vector, stored [n, 1]
        return leaf[:, 0].astype(jnp.float32)

    def ssm_branch(u, lp):
        s = u.shape[0]
        on_z, on_x, on_b, on_c, on_dt = ssm_segments
        p = low((ssm_in * u) @ plain(lp["ssm_w_in"]))    # z | x | B | C
        z, xs, b, c = jnp.split(p, [inner, 2 * inner, 2 * inner + bc],
                                axis=-1)
        z = low(z * on_z)
        xbc = low(jnp.concatenate([xs * on_x, b * on_b, c * on_c], axis=-1))
        dt = low(low((ssm_in * u) @ plain(lp["ssm_w_dt"])) * on_dt)
        w_c = lp["ssm_conv_w"].astype(jnp.float32)              # [taps, C]
        if conv:
            padded = jnp.concatenate(
                [jnp.zeros((taps_n - 1, xbc.shape[-1]), jnp.float32), xbc])
            acc = sum(w_c[j] * padded[j:j + s] for j in range(taps_n))
        else:
            acc = w_c[taps_n - 1] * xbc
        xbc = low(jax.nn.silu(acc + vec(lp["ssm_conv_bias"])))
        xs, b, c = jnp.split(xbc, [inner, inner + bc], axis=-1)
        xs = xs.reshape(s, heads, head_dim)
        per = heads // groups
        b = jnp.repeat(b.reshape(s, groups, state_n), per, axis=1)
        c = jnp.repeat(c.reshape(s, groups, state_n), per, axis=1)
        dt = jax.nn.softplus(dt + vec(lp["ssm_dt_bias"]))       # [S, heads]
        a = -jnp.exp(vec(lp["ssm_a_log"]))

        def step(carried, t):
            x_t, b_t, c_t, dt_t = t
            carried = (jnp.exp(dt_t * a)[:, None, None] * carried
                       + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
            carried = rounded(carried, state)
            return carried, jnp.einsum("hpn,hn->hp", carried, c_t)

        y = jnp.zeros_like(xs)
        if recurrence:
            _, y = jax.lax.scan(
                step, jnp.zeros((heads, head_dim, state_n), jnp.float32),
                (xs, b, c, dt))
        if skip:
            y = y + vec(lp["ssm_d"])[:, None] * xs
        y = y.reshape(s, inner)

        def grouped_norm(y):
            part = y.reshape(s, groups, -1)
            part = part / jnp.sqrt(
                jnp.mean(part * part, axis=-1, keepdims=True) + eps)
            return (part.reshape(s, inner)
                    * lp["ssm_gate_norm"].astype(jnp.float32))

        if gate_before_norm:
            y = grouped_norm(y * jax.nn.silu(z))
        else:
            y = grouped_norm(y) * jax.nn.silu(z)
        return low(ssm_out * low(low(y) @ plain(lp["ssm_w_out"])))

    def attention(u, lp):
        s = u.shape[0]
        u_a = attn_in * u
        q = low(u_a @ plain(lp["wq"])).reshape(s, nh, d)
        k = low(key_mult * low(u_a @ plain(lp["wk"]))).reshape(s, nkv, d)
        v = low(u_a @ plain(lp["wv"])).reshape(s, nkv, d)
        if rope_on:
            q, k = low(rope(q, theta)), low(rope(k, theta))
        k = jnp.repeat(k, nh // nkv, axis=1)
        v = jnp.repeat(v, nh // nkv, axis=1)
        seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

        def one_head(qkv):      # a head at a time: 5,000 x 5,000 scores
            q_h, k_h, v_h = qkv
            scores = jnp.where(seen, q_h @ k_h.T * d ** -0.5, -jnp.inf)
            return jax.nn.softmax(scores, axis=-1) @ v_h

        out = jax.lax.map(one_head, tuple(
            t.transpose(1, 0, 2) for t in (q, k, v)))           # [nh, S, d]
        out = out.transpose(1, 0, 2).reshape(s, nh * d)
        return low(attn_out * low(low(out) @ plain(lp["wo"])))

    def feed_forward(x, lp, norm):
        on_gate, on_down = mlp
        v = low(rms_norm(low(x), norm, eps))
        up = low(v @ plain(lp["w_up"]))
        gate = jax.nn.silu(on_gate * low(v @ plain(lp["w_gate"])))
        return x + low(on_down * low(low(up * gate) @ plain(lp["w_down"])))

    def layer(x, layers, index):
        lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, index, 0, keepdims=False),
            {k: v for k, v in layers.items() if k != "mixer_norm"})
        norm_in = layers["mixer_norm"][2 * index]
        norm_ff = layers["mixer_norm"][2 * index + 1]
        u = low(rms_norm(low(x), norm_in, eps))
        out_s = ssm_branch(u, lp) if ssm else 0.0
        if attn:
            # The sequential group: attention behind the mixer's sum.
            u_a = u if parallel else low(rms_norm(low(x + out_s), norm_in,
                                                  eps))
            out_a = attention(u_a, lp)
        else:
            out_a = 0.0
        x = low(x + out_s + out_a)
        return feed_forward(x, lp, norm_ff) if mlp_on else x

    return jax.jit(layer)


def layer_of(spec, **switches):
    """``layer(x, layers, index)`` of ``spec`` (a ModelSpec as the program's
    ``from_hf_config`` reads the configuration)."""
    if not getattr(spec, "parallel_mixers", False):
        raise TypeError(f"{type(spec).__name__} has no mixers side by side: "
                        "not the Falcon-H1 block")
    for key in BOOLS:
        if isinstance(switches.get(key), str):
            switches[key] = switches[key].lower() not in ("false", "0", "no")
    for key in FLOATS:
        if key in switches:
            switches[key] = float(switches[key])
    for key in ("rope", "mlp"):
        if key in switches:
            switches[key + "_on"] = switches.pop(key)
    return _layers(
        spec.ssm_heads, spec.ssm_head_dim, spec.ssm_groups, spec.ssm_state,
        spec.ssm_conv, spec.num_heads, spec.num_kv_heads, spec.head_dim,
        float(spec.rms_norm_eps), float(spec.rope_theta),
        float(spec.key_multiplier), float(spec.attn_in_multiplier),
        float(spec.attn_out_multiplier), float(spec.ssm_in_multiplier),
        tuple(spec.ssm_multipliers), float(spec.ssm_out_multiplier),
        tuple(spec.mlp_multipliers), tuple(sorted(switches.items())))


@functools.cache
def _layers(*args):
    *dims, switches = args
    return make_layers(*dims, **dict(switches))


@functools.cache
def _head_fn(eps: float, multiplier: float, chunks: int):
    import jax
    import jax.numpy as jnp

    def head(x, final_norm, table):
        h = rms_norm(x, final_norm, eps)
        width = table_shape(table)[1] // chunks

        def logits_of(c):
            # One slice of the vocabulary at a time (lib/reference.py).
            part = jax.tree.map(lambda a: jax.lax.dynamic_slice_in_dim(
                a, c * width, width, 1) if a.shape[1] != 1 else a, table)
            return multiplier * (h @ plain(part))

        parts = jax.lax.map(logits_of, jnp.arange(chunks))  # [C, S, width]
        logits = jnp.moveaxis(parts, 0, 1).reshape(x.shape[0], -1)
        return jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)

    return jax.jit(head)


def logprobs_from(params, spec, tokens, first: int, layer,
                  skip_layer: int | None = None):
    """[len(tokens) - first, vocab] float32: the log-probabilities of the
    token after each of ``tokens`` from position ``first`` on, under the
    plain forward of ``tokens``: the embedding times ``embedding_multiplier``
    (``spec.scale_emb``), the layers, the final norm, the head's logits
    times ``lm_head_multiplier`` (1 / ``spec.logit_divisor``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    tokens = np.asarray(tokens, np.int32)
    with jax.default_matmul_precision("highest"):
        embed = params["embed"]
        x = (embed.q[tokens] if hasattr(embed, "q") else embed[tokens]
             ).astype(jnp.float32)
        if hasattr(embed, "s"):
            x = x * embed.s.astype(jnp.float32)[0]
        x = x * spec.scale_emb
        for index in range(spec.num_layers):
            if index != skip_layer:
                x = layer(x, params["layers"], jnp.int32(index))
        table = params["lm_head"]
        vocab = table_shape(table)[1]
        chunks = next(c for c in (8, 4, 2, 1) if vocab % c == 0)
        return _head_fn(float(spec.rms_norm_eps),
                        1.0 / float(spec.logit_divisor), chunks)(
            x[first:], params["final_norm"], table)


def teacher_forced(params, spec, prompt: list[int], generated: list[int],
                   layer, skip_layer: int | None = None) -> list[float]:
    """Logprob of each generated token under the plain forward of
    ``prompt + generated[:-1]``."""
    import jax.numpy as jnp
    import numpy as np
    n_gen = len(generated)
    logp = logprobs_from(params, spec, list(prompt) + list(generated[:-1]),
                         len(prompt) - 1, layer, skip_layer)
    picked = logp[jnp.arange(n_gen), jnp.asarray(generated, jnp.int32)]
    return [float(v) for v in np.asarray(picked, np.float64)]


def reference_logprobs(params, spec, prompt: list[int],
                       generated: list[int], skip_layer: int | None = None
                       ) -> list[float]:
    return teacher_forced(params, spec, prompt, generated, layer_of(spec),
                          skip_layer)


def control_logprobs(params, spec, prompt: list[int], generated: list[int],
                     **switches) -> list[float]:
    """``reference_logprobs`` with ``make_layers``' switches: what a port
    with that one equation wrong would give."""
    return teacher_forced(params, spec, prompt, generated,
                          layer_of(spec, **switches))
