"""Plain reference of the GLM-4.7-Flash block (zai-org/GLM-4.7-Flash,
``model_type`` ``glm4_moe_lite``) for ONE share of an expert-parallel
deployment, and of its multi-token-prediction module: latent attention in the
EXPANDED form with NO indexer (every query attends every earlier key), a
leading dense layer, a sigmoid router with a selection bias as wide as the
deployment has experts, of which the parameters hold ``num_experts`` from
``first_expert`` on, one shared expert, an untied head, and the prediction
module teacher-forced. ``jax.numpy`` only, float32, ``highest`` precision,
over the parameters as stored (int8 leaves dequantised: q * s); nothing of
engine/model.py, no cache, no absorbed product, no drafting. ``plain`` is
lib/reference.py's.

Main model, 47 layers, layer 0 dense (SwiGLU 10,240), layers 1 to 46 expert
layers, RMSNorm eps 1e-5 (RMS(x; w) = x / sqrt(mean(x^2) + eps) * w), pre-norm
residual block (attention, then feed-forward), untied head of 154,880 rows.

- Attention (a layer), h = RMS(x; w_in): cq = RMS(h Wq_a; w_q) (768);
  q = cq Wq_b -> 20 heads of (192 nope | 64 rope); (c | kr) = h Wkv_a
  (512 | 64), c = RMS(c; w_kv); rope at theta 1e6 on q's rope part and on kr,
  INTERLEAVED pairs (2i, 2i + 1), no scaling (``rope_scaling`` null); head j:
  k_j = (c Wk_b[j] | kr) (192 | 64), v_j = c Wv_b[j] (256); scores
  q_j . k_j x 256^-0.5, causal, EVERY key; output concat_j(softmax v_j) Wo
  (5,120 -> 2,048). x1 = x + attention; h2 = RMS(x1; w_post).
- The dense layer: x' = x1 + (silu(h2 Wg) * (h2 Wu)) Wd at 10,240.
- Expert layer: s = sigmoid(h2 Wr) over 64 in float32; choice = the 4 largest
  of s + bias (``n_group`` 1, ``topk_group`` 1: no groups); gates = chosen s
  over their sum (over all 4, wherever they are held), x 1.8; each expert
  SwiGLU of width 1,536; plus ONE shared expert of 1,536, added:
  x' = x1 + sum over the chosen AND HELD HERE of g_e E(h2; W_e) + E(h2; W_sh).
  What the experts held elsewhere would add is left out, here as in the
  program (one chip of four runs without its exchange).
- logits = RMS(x_L; w_f) W_head.
- **Prediction module** (``num_nextn_predict_layers`` 1; checkpoint layer
  47), for position i with the main model's output h_i = RMS(x_L[i]; w_f) and
  the NEXT token t_{i+1}: x_i = [RMS(Emb(t_{i+1}); w_e) ; RMS(h_i; w_h)] W_eh
  (4,096 -> 2,048); y_i = Block_47(x_0..x_i)_i, a whole expert layer of the
  kind above over its OWN keys, rope position i; draft logits for t_{i+2} =
  RMS(y_i; w_s) W_head with the main model's embedding and head (shared, not
  copied). ``draft_logits`` computes them teacher-forced; the draft is the
  argmax.

Departures and assumptions (the configuration's file lists the same under
``assumed``; there is no network here to read the model's code): rope pairing
interleaved (the catalog row has no key; as the DeepSeek-V3.2 block's);
``scoring_func`` sigmoid and ``moe_layer_freq`` 1 (keys the catalog dropped);
the module's input order is embedding half of W_eh first (as the published
checkpoints of the family lay ``eh_proj`` out; the DeepSeek-V3 paper writes
the hidden half first); h_i is taken AFTER the main model's final norm and
normed again by w_h; the module's rope position for x_i is i; kv_b_proj is two
leaves (Wk_b, Wv_b): the same numbers.

``make_layer``'s keywords switch ONE equation each to what a careless port
would compute: ``bias`` false (choice by s alone), ``scaling`` (1: no routed
scaling factor), ``shared`` false (the shared expert left out), ``scale``
(e.g. 192: ``192^-0.5``), ``kv_norm`` false (c unnormed); ``precision``
computes every tensor the configuration's dtype holds in "bfloat16" or
"float8_e4m3fn". ``parts`` returns the feed-forward's terms apart, for the
test that the four shares add up to the uncut layer.

ALLOWED_NATS, measured on one v5e at the cell's size (47 layers and the
module, int8 weights, 16 of 64 experts held; the check's 4 prompts x 16 tokens
after 64-token prompts, served by the DRAFTING window; my chip runs, PR 39,
calls 1 to 3 and 6: fifteen seeds, each its own weights and words; against
what was served; nat, smallest to largest over the seeds; the controls by
``benchmark/draft_check.py``, calls 3 and 6, two seeds where a range is
given):

                                   median         root mean sq.  worst token
    this reference, 15 seeds       0.040-0.110    0.100-0.197    0.316-1.051
    computed in bfloat16           0.085          0.214          1.210
    computed in float8 (e4m3)      1.151-1.226    1.265-1.371    2.802-2.996
    scaling=1                      0.214-0.352    0.333-0.489    1.066-1.457
    bias=false                     1.049          1.129          2.257
    shared=false                   2.102          2.367          4.349

MEDIAN 0.16 is 1.45 times the largest median a sound run read (twice the
largest of thirteen of the fifteen) and three quarters of the smallest the
nearest control read (scaling=1; a seventh of float8's); RMS 0.4 is twice the
largest sound reading and a third of float8's smallest (scaling=1's 0.33 to
0.49 straddles it: the median tells that control); WORST 2.5 is 2.4 times the
largest sound reading (ONE token of one seed at 1.05, another at 0.93, the
others under 0.62) and under float8's 2.8; it is there to catch a fault in a
few tokens, which moves them by whole nats. The float8 forward, the nearest
precision below the configuration's bfloat16 activations, fails by the median
and by the root mean square, as do the three named controls by the median;
bfloat16, the served precision, passes.
"""

from __future__ import annotations

import functools

from benchmark.lib.reference import plain
# The block kind this reference is of, as the program's reader states it: a
# program whose reader knows no ``glm4_moe_lite`` fails in run.py before
# anything is launched.
from dynamo_tpu.engine.config import DeepseekV32Spec, MTP_PREFIX  # noqa: F401

#: Largest median, root mean square and worst absolute difference (nat) of
#: the 64 served logprobs from this forward that pass (the docstring's table).
ALLOWED_NATS = {"median": 0.16, "rms": 0.4, "worst": 2.5}

DENSE_PREFIX = "dense_"
MODULE_PREFIX = "mtp_"


def rms_norm(x, scale, eps):
    import jax.numpy as jnp
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale.astype(jnp.float32)


def rope(x, theta: float):
    """x [S, heads, D] at positions 0..S-1: frequency theta^(-2i/D) turns
    the INTERLEAVED pair (2i, 2i + 1)."""
    import jax.numpy as jnp
    s, _, d = x.shape
    freqs = theta ** (-2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


@functools.cache
def make_layer(nh: int, nope: int, rdim: int, vdim: int, rank: int,
               eps: float, theta: float, top_k: int, factor: float,
               first_expert: int, held: int, *, bias: bool = True,
               scaling: float | None = None, shared: bool = True,
               scale: float | None = None, kv_norm: bool = True,
               precision: str = "float32", parts: bool = False):
    import jax
    import jax.numpy as jnp

    sm_scale = (nope + rdim) ** -0.5 if scale is None else float(scale) ** -0.5
    gate_scale = factor if scaling is None else float(scaling)

    def low(a):
        """A tensor the configuration's dtype holds, as ``precision`` does."""
        if precision == "float32":
            return a
        return a.astype(getattr(jnp, precision)).astype(jnp.float32)

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

    def attention(x, lp):
        s = x.shape[0]
        h = low(rms_norm(x, lp["input_norm"], eps))
        cq = low(rms_norm(h @ plain(lp["wq_a"]), lp["q_a_norm"], eps))
        q = (cq @ plain(lp["wq_b"])).reshape(s, nh, nope + rdim)
        ckv = h @ plain(lp["wkv_a"])
        c = ckv[:, :rank]
        if kv_norm:
            c = rms_norm(c, lp["kv_a_norm"], eps)
        c = low(c)
        kr = low(rope(ckv[:, None, rank:], theta)[:, 0])
        kn = low(c @ plain(lp["wk_b"])).reshape(s, nh, nope)
        v = low(c @ plain(lp["wv_b"])).reshape(s, nh, vdim)
        qn = low(q[..., :nope])
        qr = low(rope(q[..., nope:], theta))
        seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

        def one_head(qkv):
            qn_h, qr_h, kn_h, v_h = qkv
            scores = (qn_h @ kn_h.T + qr_h @ kr.T) * sm_scale
            scores = jnp.where(seen, scores, -jnp.inf)
            return jax.nn.softmax(scores, axis=-1) @ v_h

        attn = jax.lax.map(one_head, tuple(
            a.transpose(1, 0, 2) for a in (qn, qr, kn, v)))      # [nh, S, v]
        attn = attn.transpose(1, 0, 2).reshape(s, nh * vdim)
        return low(attn) @ plain(lp["wo"])

    def routed(h2, lp):
        s = h2.shape[0]
        score = jax.nn.sigmoid(h2 @ lp["moe_gate"].astype(jnp.float32))
        z = score
        if bias:
            z = z + lp["moe_bias"][:, 0].astype(jnp.float32)
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

    def layer(x, stack, index):
        """``stack``: the leaves of the layers of ONE kind (the leading
        dense layer's and the module's under the names without their
        prefix, or the expert layers'), ``index`` the layer among them,
        cut out inside the compiled function."""
        lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, index, 0, keepdims=False), stack)
        x = low(x)
        a = attention(x, lp)
        x1 = low(x + a)
        h2 = low(rms_norm(x1, lp["post_attn_norm"], eps))
        if "moe_gate" not in lp:
            return x1 + ffn(h2, lp["w_gate"], lp["w_up"], lp["w_down"])
        chosen = routed(h2, lp)
        ones = jnp.ones((x.shape[0], 1), jnp.float32)
        own = experts(h2, lp["shared_w_gate"], lp["shared_w_up"],
                      lp["shared_w_down"], ones)
        if parts:
            return {"attention": a, "routed": chosen, "shared": own}
        return x1 + chosen + (own if shared else 0.0)

    return jax.jit(layer)


def layer_of(spec, **switches):
    """``layer(x, stack, index)`` of ``spec`` (a ModelSpec as the program's
    ``from_hf_config`` reads the configuration): its share is experts
    ``first_expert`` to ``first_expert + num_experts - 1`` of a router
    ``router_width`` wide, and the parameters hold those."""
    if not isinstance(spec, DeepseekV32Spec) or spec.index_topk:
        raise TypeError(f"{type(spec).__name__} (index_topk "
                        f"{getattr(spec, 'index_topk', None)}) is not the "
                        "latent block without an indexer")
    for key in ("scaling", "scale"):
        if key in switches:
            switches[key] = float(switches[key])
    return make_layer(
        spec.num_heads, spec.qk_nope_head_dim, spec.qk_rope_head_dim,
        spec.v_head_dim, spec.kv_lora_rank, float(spec.rms_norm_eps),
        float(spec.rope_theta), spec.num_experts_per_tok,
        float(spec.routed_scaling_factor), spec.first_expert,
        spec.num_experts, **switches)


def _stacks(params):
    stacked = params["layers"]
    first = {k[len(DENSE_PREFIX):]: v for k, v in stacked.items()
             if k.startswith(DENSE_PREFIX)}
    module = {k[len(MODULE_PREFIX):]: v for k, v in stacked.items()
              if k.startswith(MODULE_PREFIX)}
    rest = {k: v for k, v in stacked.items()
            if not k.startswith((DENSE_PREFIX, MODULE_PREFIX))}
    return first, rest, module


def layers_of(params, spec):
    """(stack, index) a layer of the MAIN model, in its order: the leading
    dense layers' leaves (``dense_<name>``, the prefix taken off), then the
    expert layers'. The module's leaves (``mtp_<name>``) are no layer of
    it."""
    first, rest, _ = _stacks(params)
    dense = spec.first_k_dense
    return ([(first, i) for i in range(dense)]
            + [(rest, i) for i in range(spec.num_layers - dense)])


def embedding(params, tokens):
    import jax.numpy as jnp
    import numpy as np
    tokens = np.asarray(tokens, np.int32)
    embed = params["embed"]
    x = (embed.q[tokens] if hasattr(embed, "q") else embed[tokens]).astype(
        jnp.float32)
    if hasattr(embed, "s"):
        x = x * embed.s.astype(jnp.float32)[0]
    return x


def hidden_states(params, spec, tokens, layer, skip_layer=None):
    """The stream after the last layer for ``tokens`` [S]: the embedding's
    rows, then ``layer`` for each layer but ``skip_layer``."""
    import jax.numpy as jnp
    x = embedding(params, tokens)
    for index, (stack, at) in enumerate(layers_of(params, spec)):
        if index != skip_layer:
            x = layer(x, stack, jnp.int32(at))
    return x


@functools.cache
def _head_fn(chunks: int):
    import jax
    import jax.numpy as jnp

    def head(h, table):
        cols = (table.q if hasattr(table, "q") else table).shape[1]
        width = cols // chunks

        def logits_of(c):
            # One slice of the vocabulary at a time: its float32 copy.
            part = jax.tree.map(lambda a: jax.lax.dynamic_slice_in_dim(
                a, c * width, width, 1), table)
            return h @ plain(part)

        out = jax.lax.map(logits_of, jnp.arange(chunks))         # [C, S, w]
        return jnp.moveaxis(out, 0, 1).reshape(h.shape[0], -1)

    return jax.jit(head)


def head_logits(params, h):
    """The untied head over normed rows ``h``: float32 logits."""
    table = params["lm_head"]
    cols = (table.q if hasattr(table, "q") else table).shape[1]
    chunks = next(c for c in (8, 4, 2, 1) if cols % c == 0)
    return _head_fn(chunks)(h, table)


def teacher_forced(params, spec, prompt, generated, layer,
                   skip_layer=None) -> list[float]:
    """Logprob of each generated token under the plain forward of
    ``prompt + generated[:-1]``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    n_prompt, n_gen = len(prompt), len(generated)
    eps = float(spec.rms_norm_eps)
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, spec, list(prompt) + list(generated[:-1]),
                          layer, skip_layer)
        # Only the positions that predict a generated token reach the head.
        h = rms_norm(x[n_prompt - 1:n_prompt - 1 + n_gen],
                     params["final_norm"], eps)
        logp = jax.nn.log_softmax(head_logits(params, h), axis=-1)
        picked = logp[jnp.arange(n_gen), jnp.asarray(generated, jnp.int32)]
    return [float(v) for v in np.asarray(picked, np.float64)]


def reference_logprobs(params, spec, prompt: list[int],
                       generated: list[int], skip_layer: int | None = None
                       ) -> list[float]:
    return teacher_forced(params, spec, prompt, generated, layer_of(spec),
                          skip_layer)


#: The name the issue gives check (a)'s function; run.py asks for
#: ``reference_logprobs``.
logprobs = reference_logprobs


def control_logprobs(params, spec, prompt: list[int], generated: list[int],
                     **switches) -> list[float]:
    """``reference_logprobs`` with ``make_layer``'s switches: what a port
    with that one equation wrong would give."""
    return teacher_forced(params, spec, prompt, generated,
                          layer_of(spec, **switches))


def draft_logits(params, spec, tokens: list[int], **switches):
    """The prediction module teacher-forced over ``tokens`` t_0..t_{n-1}:
    float32 [n - 1, V], row i the draft logits for t_{i+2} from h_i (the
    main model's normed output at position i) and t_{i+1}. Row i's argmax
    is what a drafting engine proposes after it has emitted t_{i+1}."""
    import jax
    import jax.numpy as jnp
    eps = float(spec.rms_norm_eps)
    layer = layer_of(spec, **switches)
    _, _, module = _stacks(params)
    own = lambda name: jax.tree.map(  # noqa: E731 — a stack of one
        lambda a: a[0], module[name])
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, spec, tokens, layer)
        h = rms_norm(x, params["final_norm"], eps)[:-1]
        e = rms_norm(embedding(params, tokens[1:]), own("e_norm"), eps)
        xm = jnp.concatenate([e, rms_norm(h, own("h_norm"), eps)], axis=-1) \
            @ plain(own("w_eh"))
        block = {k: v for k, v in module.items()
                 if k not in ("w_eh", "e_norm", "h_norm", "head_norm")}
        y = layer(xm, block, jnp.int32(0))
        return head_logits(params, rms_norm(y, own("head_norm"), eps))
