"""The programs of a block whose layers are GROUPS of sublayers of ONE mixer
or feed-forward each, every sublayer ``h <- h + a f(RMS(h; w, eps))`` with
``a = spec.residual_scale``.

``spec.layer_pattern`` gives every sublayer its kind, a letter each
(config.GROUP):

- **M, Mamba-2.** ``[z | xBC | dt] = u W_in``; ``xBC_t = silu(b_c + sum_j
  w_c[j] xBC_{t-K+1+j})`` (causal, depthwise, K taps, zeros before the
  sequence); ``[x | B | C] = xBC``; ``dt_t = softplus(dt_t + dt_bias)``;
  ``A = -exp(A_log)``; for head h of group g, state S [head_dim, state]
  float32: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t[g]``,
  ``y_t = S_t C_t[g] + D[h] x_t``; ``y = y silu(z)``, RMS-normalised within
  each group, times a weight; ``out = y W_out``. A row keeps S and the last
  K - 1 inputs of the convolution, a layer (``spec.ssm_state_shapes``); the
  runner's array of the latter is TAPS-MAJOR, [M, K - 1, slots, channels]
  (``spec.conv_state_shape``): a tap is a whole plane [slots, channels]
  whose tiles are full, and a step shifts planes.
- **L, lightning linear attention.** ``[q | k | v | z] = u W_in``, heads of
  ``ssm_head_dim``; q and k RMS-normalised a head (times a weight) and
  rotated (rotate-half, every lane); a head's constant decay ``lambda_h``
  (``lightning_decay``); state S [head_dim (v), head_dim (k)] float32 a
  head: ``S_t = lambda_h S_{t-1} + v_t (x) k_t``, ``o_t = S_t q_t /
  sqrt(head_dim)``: the recurrence above with ``dx = v``, ``B = k``,
  ``C = q / sqrt(d)``, a group a head, no convolution and no D. ``o`` is
  RMS-normalised a head (times a weight), times ``sigmoid(z)``; ``out = o
  W_out``. The SAME state arrays, chunked prefill and decode kernel as M.
- **K, the gated delta rule** (Kimi Delta Attention; the Solar-Open2
  block). ``[q | k | v] = silu(conv(u W_in))`` (causal, depthwise, K taps,
  zeros before the sequence, no bias; ``ssm_heads`` heads, q and k of
  ``ssm_state``, v of ``ssm_head_dim``); q and k unit vectors a head, q over
  sqrt(state); ``g_t = -exp(A_log[h]) softplus((u W_fa) W_fb + dt_bias)``, a
  log-decay a CHANNEL of the k axis and a token; ``beta_t = ssm_beta_scale
  sigmoid(u W_b)`` a head (up to 2: ``I - beta k k^T`` may reflect); state S
  [head_dim (v), state (k)] float32 a head: ``S' = S_{t-1} Diag(e^g_t)``,
  ``S_t = S' + beta_t (v_t - S' k_t) (x) k_t``: the update READS the decayed
  state before it writes it, which neither recurrence above does; ``o_t =
  S_t q_t``, RMS-normalised a head (times ONE weight of head_dim), times
  ``sigmoid((u W_ga) W_gb)``; ``out = o W_out``. A row keeps S and the last K
  - 1 inputs of the convolution, as M does, in the same two arrays. A
  decode step is ``delta_update`` (XLA, every slot: the definition) or the
  kernel's second form (engine/recurrence.py ``delta_state_step``: the
  live slots, ONE visit of a state); prefill solves a chunk of
  ``ssm_chunk`` tokens by ONE triangular system a head (``delta_chunked``),
  every exponent a difference of cumulative log-decays that is never
  positive.
- **E, an expert layer:** model.ffn_block (sigmoid router with a selection
  bias; two-matrix relu2 experts and one shared expert of its own width, or
  SwiGLU experts and a shared one of theirs, by ``spec.ffn_act``); a
  prefill over model.MOE_DENSE_MAX_ROWS rows sends each row to its own
  experts and a window step walks the held experts its live rows chose
  (``scan_groups`` hands either kernel of engine/experts.py the expert
  stacks whole; on a mesh every held expert is multiplied). **D, a dense
  feed-forward:** the same function's dense branch (SwiGLU).
- **\\*, attention:** grouped-query, causal; K and V go to the pool, whose
  layers are the attention layers alone; where ``spec.attn_gate`` the
  output is gated by ``sigmoid(u W_z)`` as S's is. NO rotary embedding,
  unless the block says that its * layers rotate (``spec.attn_rope``, the
  Falcon-H1 block): then q and k are turned by the rows' positions
  (rotate-half, every lane, ``rope_theta``) in prefill, whole prompts and
  chunks over history alike, and in a window step, and k is multiplied by
  ``spec.key_multiplier`` AHEAD of the rotation and of the pool: what a page
  holds is what is attended.
- **S, attention over chosen BLOCKS of keys** (InfLLM-V2): as \\*, q and k
  RMS-normalised a head, and a query attends ``sparse_topk`` blocks of
  ``sparse_block`` keys a KV group: a compressed key is the mean of
  ``sparse_kernel`` keys every ``sparse_stride``; ``p_h = softmax_j(q_h .
  kc_j / sqrt(d))`` over the compressed keys whose keys are all at or
  before the query; a group's score of j is the sum of p_h over its heads,
  a block's the largest over the compressed keys that overlap it; the
  first ``sparse_init_blocks`` and the ``sparse_window / sparse_block``
  blocks that end with the query's own always stay; ties to the lower
  block. The output is gated by ``sigmoid(u W_z)``. What a token leaves
  beside K and V is its share of a STRIPE (the mean of ``sparse_stride``
  keys), in a third array under the same page table
  (``spec.comp_key_shape``): a compressed key is the mean of two stripes.
  Decode scores the stripes, chooses, and reads the chosen blocks ALONE.
  Who scores the stripes the pool holds follows the pool's reader
  (``Backends.index``, the label ``index_backend``): beside the Pallas
  reader the kernel attention.stripe_scores_pallas walks a LIVE row's
  pages of the array once, a page's stripes of both heads one copy (PR
  46); everywhere else XLA gathers every slot's whole bucket
  (``pool_stripes``) and scores the copy. The set is found by counts, not
  by a sort (``choose_blocks``: block i stays when fewer than K beat it),
  and comes out in rising order: the pool seen as blocks of one KV head
  each is walked by the reader that walks a page table
  (model.kv_attention: the Pallas kernel or XLA's gather), over a table
  [rows x KV heads, sparse_topk] of those block ids, so that the one
  partly filled block is the last and the reader's length masks it.
  Prefill masks dense scores by blocks, a chunk of queries at a time; it
  writes the array, and so does the window's commit
  (``commit_stripes``).

A group is at most one recurrent mixer, at most one attention layer, then a
feed-forward (``groups_of``): Nemotron-H's pairs of M and E with a * between
some, MiniCPM-SALA's layers of L or S and D, Solar-Open2's of K or * and E.
Its mixers are wired one of two ways:

- **one after the other** (every block above): ``h <- h + a M(RMS(h;
  w_m))``, then ``h <- h + a Attn(RMS(h; w_a))``: each sublayer behind its
  own norm and its own residual sum, a sublayer that only some groups have
  under a ``lax.cond``;
- **side by side** (``spec.parallel_mixers``, the Falcon-H1 block, whose
  every group is M * D): ``u = RMS(h; w_m)`` feeds BOTH, ``h <- h + a_s
  SSM(b_s u) + a_a Attn(b_a u)``: ONE norm in, ONE sum out, no conditional
  (every group has both), and the * sublayers have no norm of their own
  (``mixer_norm`` holds two rows a group). The muP constants sit where the
  model publishes them: ``b_s`` (``ssm_in_multiplier``) and the five
  segment constants of ``ssm_multipliers`` (z, x, B, C, dt) as ONE constant
  a column behind the in-projection (``_project``: the product is linear,
  XLA fuses the vector); ``a_s`` (``ssm_out_multiplier``) behind the
  out-projection (``_gated_out``); ``b_a`` (``attn_in_multiplier``) on the
  attention's input, ``key_multiplier`` on k ahead of the rotation
  (``_qkv``), ``a_a`` (``attn_out_multiplier``) behind W_o (``_attn_out``);
  the SwiGLU's two (``mlp_multipliers``) in model.ffn_block's dense branch.
  A row of such a block holds a state a slot AND K/V pages a token in every
  layer. It is spelled as an attribute of the spec over the pattern ``M*D``
  and not as a letter of its own: the letters name a sublayer's KIND, and
  everything that counts by kind (the ``ssm_`` leaves and the state arrays
  by M, the pool's layers by *, the feed-forward's stack by D) holds
  unedited; how a group's two mixers are wired is one fact of the block,
  read in ONE place, ``scan_groups.group``.
A model has recurrent mixers of ONE kind (``spec.ssm_kind``: they share the
``ssm_`` leaves and the state arrays). Every program here is ONE scan
over the stacked groups in which a sublayer that only some groups have lies
under a ``lax.cond`` that indexes its own stack: 52 layers unrolled, eight
steps a window, do not compile in a set-up anyone waits for. The recurrent
state rides the scan's carry and each layer updates its own rows in place;
it never rides a conditional (a branch that hands a 1.2 GB carry through
may copy it): where only some groups have a recurrent mixer its
projections lie under the ``lax.cond`` and the update runs in every group,
over no row where the group has none (``Mixer``). The pool is read-only
inside a scan as in model.py.

Prefill computes the recurrence in chunks of ``spec.ssm_chunk`` tokens as
matrix products (``chunked_recurrence``: within a chunk the decayed (C_l .
B_s) matrix times dt x, between chunks the state at each border); decode
takes one token (``ssm_step``, ``lightning_step``, ``delta_step``). Past a
row's last real token dt is 0, and for K g and beta (the state stands:
exp(0) S + 0), and the convolution keeps the
last real inputs, so a padded batch, a dead slot and a frozen row leave a
state exactly as it was.

Who updates the float32 state in a decode step (``Backends.ssm``, decided
by backends.choose as the pool's reader is, and beside it): where the
Pallas reader runs on one TPU device the kernel of engine/recurrence.py,
which is handed the stack over all layers where it lies and visits the
live slots of one layer (``ssm_step_live``: a dead slot is neither read nor
written, and the new state is read by C in the pass that writes it);
everywhere else (the CPU backend, any mesh, a runner asked for the XLA
reader) XLA through ``ssm_step`` / ``lightning_step`` / ``delta_step``,
over every slot of the layer's slice: the definitions the kernel's two
forms are held to. Prefill is XLA's under either, and so are the
convolution's carried inputs (``conv_token`` over the layer's slice of the
taps-major stack: a kernel over it won 0.2 and 0.4 % of a step and was not
kept, PERF.md section 6, PR 53).
"""

from __future__ import annotations

import math
import re
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.backends import XLA, Backends
from dynamo_tpu.engine.config import (GROUP, RECURRENT_KINDS,
                                      ModelSpec)
from dynamo_tpu.engine.kv_quant import gather_pages_folded, scatter_pages
from dynamo_tpu.engine.model import (LATENT_SCORE_BYTES, Params, _split_heads,
                                     apply_rope, dense_causal_attention,
                                     embed_lookup, expert_product, ffn_block,
                                     history_attention, kv_attention,
                                     layer_of, lm_logits, mm, rms_norm,
                                     rope_tables, times, whole_expert_leaves)
from dynamo_tpu.engine.perf import scope
from dynamo_tpu.engine.recurrence import delta_state_step, state_step

ATTN_LEAVES = ("wq", "wk", "wv", "wo", "wz", "q_norm", "k_norm")
FFN_LEAVES = ("w_gate", "w_up", "w_down")


class Groups(NamedTuple):
    """``spec.layer_pattern`` as its groups (config.GROUP): for group p the
    index among ALL sublayers of its recurrent mixer, of the attention
    layer behind it (-1: none) and of its feed-forward, and the mixer's and
    the attention layer's index in the stack of their kind (-1: none)."""
    mixer_layer: tuple
    mixer_index: tuple
    attn_layer: tuple
    attn_index: tuple
    ffn_layer: tuple


def groups_of(spec: ModelSpec) -> Groups:
    out = Groups([], [], [], [], [])
    mixers = attns = 0
    for found in re.finditer(GROUP, spec.layer_pattern):
        at, kinds = found.start(), found.group()
        mixer = kinds[0] in RECURRENT_KINDS
        attn = any(c in "*S" for c in kinds)
        out.mixer_layer.append(at if mixer else -1)
        out.mixer_index.append(mixers if mixer else -1)
        out.attn_layer.append(at + mixer if attn else -1)
        out.attn_index.append(attns if attn else -1)
        out.ffn_layer.append(found.end() - 1)
        mixers += mixer
        attns += attn
    return Groups(*(tuple(a) for a in out))


# ---------------------------------------------------------------------------
# The Mamba-2 mixer
# ---------------------------------------------------------------------------

def _project(h: jax.Array, lp: dict, spec: ModelSpec):
    """[z | xBC | dt] = h W_in (stored as z | xBC and dt:
    model._recurrent_shapes)."""
    zx = mm(h, lp["ssm_w_in"], "...h,hd->...d")
    z, xbc = jnp.split(zx, [spec.ssm_heads * spec.ssm_head_dim], axis=-1)
    dt = mm(h, lp["ssm_w_dt"], "...h,hd->...d")
    if spec.ssm_multipliers is not None:
        # muP: the mixer reads ssm_in_multiplier * h, and the segments z, x,
        # B, C, dt of what it projects are multiplied by a constant each: one
        # constant a column BEHIND the product (the product is linear).
        on_z, on_x, on_b, on_c, on_dt = (
            m * spec.ssm_in_multiplier for m in spec.ssm_multipliers)
        inner, bc = z.shape[-1], spec.ssm_groups * spec.ssm_state
        z, dt = times(z, on_z), times(dt, on_dt)
        xbc = times(xbc, np.repeat(np.float32([on_x, on_b, on_c]),
                                   [inner, bc, bc]))
    return z, xbc, dt


def _steps(dt_raw: jax.Array, lp: dict, live: jax.Array):
    """(dt, dt A) [..., heads] float32; dt is 0 where ``live`` [...] is
    not, so that the state stands there."""
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + lp["ssm_dt_bias"][:, 0].astype(jnp.float32))
    dt = jnp.where(live[..., None], dt, 0.0)
    a = -jnp.exp(lp["ssm_a_log"][:, 0].astype(jnp.float32))
    return dt, dt * a


def _split_xbc(xbc: jax.Array, spec: ModelSpec):
    """x [..., G, heads / G, head_dim], B and C [..., G, state]: a head
    reads the B and C of its group."""
    g, n = spec.ssm_groups, spec.ssm_state
    inner = spec.ssm_heads * spec.ssm_head_dim
    x, b, c = jnp.split(xbc, [inner, inner + g * n], axis=-1)
    lead = xbc.shape[:-1]
    return (x.reshape(*lead, g, spec.ssm_heads // g, spec.ssm_head_dim),
            b.reshape(*lead, g, n), c.reshape(*lead, g, n))


def _rms_within(y: jax.Array, parts: int, eps: float) -> jax.Array:
    """y [..., inner] float32 RMS-normalised within each of ``parts`` equal
    parts of its last axis (a group, a head), bfloat16."""
    split = y.reshape(*y.shape[:-1], parts, -1)
    var = jnp.mean(split * split, axis=-1, keepdims=True)
    return (split * jax.lax.rsqrt(var + eps)).reshape(y.shape).astype(
        jnp.bfloat16)


def _gated_out(y: jax.Array, z: jax.Array, lp: dict, spec: ModelSpec):
    """y silu(z), RMS-normalised within each group, times the weight,
    through W_out. y [..., inner] float32."""
    y = y * jax.nn.silu(z.astype(jnp.float32))
    y = (_rms_within(y, spec.ssm_groups, spec.rms_norm_eps)
         * lp["ssm_gate_norm"])
    return times(mm(y, lp["ssm_w_out"], "...d,dh->...h"),
                 spec.ssm_out_multiplier)


def conv_token(conv: jax.Array, new: jax.Array, taps: jax.Array,
               live: jax.Array):
    """One token of the causal depthwise convolution over every row. conv
    [K - 1, B, C] the carried inputs of ONE layer, taps-major (the
    oldest first), new [B, C] the token's inputs, taps [K, C], live [B].
    Returns (acc [B, C] float32: ``sum_j taps[j] plane[j]`` over the
    carried planes and ``new``, from the planes' dtype in float32 and in
    tap order, before any bias; conv with the token behind its last inputs
    where the row is ``live``, as it was where it is not)."""
    full = jnp.concatenate([conv, new[None].astype(conv.dtype)], axis=0)
    taps = taps.astype(jnp.float32)
    acc = taps[0] * full[0].astype(jnp.float32)
    for j in range(1, full.shape[0]):
        acc = acc + taps[j] * full[j].astype(jnp.float32)
    return acc, jnp.where(live[None, :, None], full[1:], conv)


def _token(h: jax.Array, lp: dict, spec: ModelSpec, conv: jax.Array,
           live: jax.Array):
    """``_token_of`` of h's projections behind their z, and conv [K - 1, B,
    C] one token on (``conv_token``)."""
    parts = _project(h, lp, spec)
    acc, conv = conv_token(conv, parts[1], lp["ssm_conv_w"], live)
    return (parts[0], *_token_of(parts, lp, spec, acc, live), conv)


def _token_of(parts: tuple, lp: dict, spec: ModelSpec, acc: jax.Array,
              live: jax.Array):
    """What one token a row hands the recurrence: (x [B,G,Hg,P], B and C
    [B,G,N], dt and dt A [B, heads], all float32) of its projections
    ``parts`` (``_project``) and the convolution's sum ``acc`` [B, C]
    float32 (``conv_token``)."""
    xbc = jax.nn.silu(acc + lp["ssm_conv_bias"][:, 0].astype(jnp.float32))
    return (*_split_xbc(xbc, spec), *_steps(parts[2], lp, live))


def _skip(lp: dict, x: jax.Array):
    """D x: what a token gives its own output past the state."""
    g, hg = x.shape[1], x.shape[2]
    return lp["ssm_d"][:, 0].astype(jnp.float32).reshape(g, hg, 1) * x


def state_update(state: jax.Array, decay: jax.Array, dx: jax.Array,
                 bb: jax.Array, cc: jax.Array):
    """One token of the recurrence over every row, XLA's: state [B, heads,
    head_dim, N] float32, decay [B, heads], dx [B, G, Hg, P], bb and cc
    [B, G, N]: ``S <- decay S + dx (x) B``; returns (``S C`` [B, G, Hg, P],
    S). What the kernel of engine/recurrence.py is held to."""
    b, g, hg, _ = dx.shape
    grouped = state.reshape(b, g, hg, *state.shape[2:])
    grouped = (decay.reshape(b, g, hg, 1, 1) * grouped
               + dx[..., None] * bb[:, :, None, None, :])
    y = jnp.sum(grouped * cc[:, :, None, None, :], axis=-1)
    return y, grouped.reshape(state.shape)


def ssm_step(h: jax.Array, lp: dict, spec: ModelSpec, state: jax.Array,
             conv: jax.Array, live: jax.Array):
    """One token a row. h [B, hidden] (normed), state [B, heads, head_dim,
    state] float32, conv [K - 1, B, channels], live [B]. Returns (out [B,
    hidden], state, conv); a row that is not live keeps both."""
    b = h.shape[0]
    z, x, bb, cc, dt, da, conv = _token(h, lp, spec, conv, live)
    dx = dt.reshape(*x.shape[:3], 1) * x                       # [B,G,Hg,P]
    y, state = state_update(state, jnp.exp(da), dx, bb, cc)
    y = y + _skip(lp, x)
    return _gated_out(y.reshape(b, -1), z, lp, spec), state, conv


def live_walk(live: jax.Array) -> tuple:
    """(slots [B] int32, count int32) of ``live`` [B]: the live slots in
    order, then entries nobody reads. The ONE count of a step's live rows:
    what the kernel visits and what the window reports."""
    return (jnp.nonzero(live, size=live.shape[0], fill_value=0)[0].astype(
        jnp.int32), jnp.sum(live.astype(jnp.int32)))


def ssm_step_live(h: jax.Array, lp: dict, spec: ModelSpec, states: jax.Array,
                  layer: jax.Array, conv: jax.Array, live: jax.Array,
                  walk: tuple, interpret: bool = False):
    """``ssm_step`` for the rows that are live, in place: ``states`` is the
    stack [M, B, heads, head_dim, state] over every recurrent layer, of
    which the kernel of engine/recurrence.py visits layer ``layer``'s slots
    ``walk`` (``live_walk``), the live ones. Returns (out, states,
    conv); a dead row's ``out`` is what a mixer makes of y = D x."""
    b = h.shape[0]
    z, x, bb, cc, dt, da, conv = _token(h, lp, spec, conv, live)
    dx = dt.reshape(*x.shape[:3], 1) * x
    states, y = state_step(states, layer, *walk, jnp.exp(da),
                           dx.reshape(b, dt.shape[1], -1), bb, cc,
                           interpret=interpret)
    y = y.reshape(x.shape) + _skip(lp, x)
    return _gated_out(y.reshape(b, -1), z, lp, spec), states, conv


def chunked_recurrence(x: jax.Array, bb: jax.Array, cc: jax.Array,
                       dt: jax.Array, da: jax.Array, state: jax.Array,
                       q: int, skip: jax.Array | None = None):
    """The recurrence over a chunk of a prompt a row, in chunks of ``q``
    tokens as matrix products. x [B,S,G,Hg,P], bb and cc [B,S,G,N], dt and
    da [B,S,heads] (the step and its log-decay, both 0 past a row's last
    real token), all float32; ``state`` [B, heads, P, N] what the rows hold
    as the chunk starts; ``skip`` [G,Hg,1] (None: none) what a token gives
    its own output past the state. Returns (y [B, S, heads * P] float32,
    the state at each row's LAST REAL token)."""
    b, s, g, hg, _ = x.shape
    q = min(q, s)
    pad = -s % q
    if pad:     # dt 0 and x 0 past the end: the state stands
        x, bb, cc, dt, da = (jnp.pad(a, ((0, 0), (0, pad))
                                     + ((0, 0),) * (a.ndim - 2))
                             for a in (x, bb, cc, dt, da))
    nc = (s + pad) // q
    chunks = lambda a: a.reshape(b, nc, q, *a.shape[2:])  # noqa: E731
    x, bb, cc = chunks(x), chunks(bb), chunks(cc)
    dt = chunks(dt).reshape(b, nc, q, g, hg)
    # cum[l]: the log-decay from the chunk's start through token l.
    cum = jnp.cumsum(chunks(da).reshape(b, nc, q, g, hg), axis=2)
    cum_h = jnp.moveaxis(cum, 2, -1)                        # [B,nc,G,Hg,Q]
    bb16, cc16 = bb.astype(jnp.bfloat16), cc.astype(jnp.bfloat16)
    # Within a chunk: y_l = sum_{s <= l} (C_l . B_s) exp(cum_l - cum_s)
    # dt_s x_s, as two matrix products.
    cb = jnp.einsum("bclgn,bcsgn->bcgls", cc16, bb16,
                    preferred_element_type=jnp.float32)
    seg = cum_h[..., :, None] - cum_h[..., None, :]
    causal = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
    mix = (cb[:, :, :, None] * jnp.exp(jnp.where(causal, seg, -jnp.inf))
           ).astype(jnp.bfloat16)                           # [B,nc,G,Hg,Q,Q]
    xdt = x * dt[..., None]                                 # [B,nc,Q,G,Hg,P]
    y = jnp.einsum("bcghls,bcsghp->bclghp", mix, xdt.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    # What a chunk adds to the state at its end, and how it decays what it
    # found: S_end = exp(cum_Q) S_start + sum_s exp(cum_Q - cum_s) dt_s x_s
    # (x) B_s.
    to_end = jnp.moveaxis(jnp.exp(cum_h[..., -1:] - cum_h), -1, 2)
    added = jnp.einsum("bcsghp,bcsgn->bcghpn",
                       (xdt * to_end[..., None]).astype(jnp.bfloat16), bb16,
                       preferred_element_type=jnp.float32)
    through = jnp.exp(cum_h[..., -1])                       # [B,nc,G,Hg]

    def border(carried, chunk):
        add, thru, c_c, cum_c = chunk
        # What the state at the chunk's start gives token l: C_l . (exp(
        # cum_l) S_start).
        y_in = jnp.einsum("bqgn,bghpn->bqghp", c_c,
                          carried.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
        y_in = y_in * jnp.exp(cum_c)[..., None]
        return thru[..., None, None] * carried + add, y_in

    lead = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
    grouped, y_in = jax.lax.scan(
        border, state.reshape(b, g, hg, *state.shape[2:]),
        (lead(added), lead(through), lead(cc16), lead(cum)))
    y = y + jnp.moveaxis(y_in, 0, 1)
    if skip is not None:
        y = y + skip * x
    return y.reshape(b, nc * q, -1)[:, :s], grouped.reshape(state.shape)


def ssm_chunked(h: jax.Array, lp: dict, spec: ModelSpec, state: jax.Array,
                conv: jax.Array, valid: jax.Array, seq_lens: jax.Array):
    """A chunk of a prompt a row. h [B, S, hidden] (normed), ``state`` and
    ``conv`` what the rows hold as the chunk starts (zeros at position 0;
    conv [B, K - 1, channels], a ROW first: prefill's ``of_rows`` turns the
    taps-major stack's), valid [B, S], seq_lens [B] the real tokens. Returns
    (out [B, S, hidden], state and conv at each row's LAST REAL token)."""
    parts = _project(h, lp, spec)
    y, state, conv = _ssm_chunk(parts, lp, spec, state, conv, valid,
                                seq_lens)
    return _gated_out(y, parts[0], lp, spec), state, conv


def _ssm_chunk(parts: tuple, lp: dict, spec: ModelSpec, state: jax.Array,
               conv: jax.Array, valid: jax.Array, seq_lens: jax.Array):
    """``ssm_chunked`` between its projections: (y [B, S, inner] float32,
    state, conv)."""
    _, xbc, dt_raw = parts
    s = xbc.shape[1]
    taps_n = spec.ssm_conv
    full = jnp.concatenate([conv, xbc.astype(conv.dtype)], axis=1)
    taps = lp["ssm_conv_w"].astype(jnp.float32)
    acc = lp["ssm_conv_bias"][:, 0].astype(jnp.float32)
    for j in range(taps_n):
        acc = acc + taps[j] * full[:, j:j + s].astype(jnp.float32)
    xbc = jax.nn.silu(acc)
    # The last K - 1 real inputs: input t lies at t + K - 1 of ``full``.
    last = seq_lens[:, None] + jnp.arange(taps_n - 1)[None, :]
    conv = jnp.take_along_axis(full, last[:, :, None], axis=1)
    x, bb, cc = _split_xbc(xbc, spec)   # [B,S,G,Hg,P], [B,S,G,N] float32
    g, hg = x.shape[2], x.shape[3]
    dt, da = _steps(dt_raw, lp, valid)                      # [B, S, heads]
    y, state = chunked_recurrence(
        x, bb, cc, dt, da, state, spec.ssm_chunk,
        lp["ssm_d"][:, 0].astype(jnp.float32).reshape(g, hg, 1))
    return y, state, conv


# ---------------------------------------------------------------------------
# The lightning linear-attention mixer
# ---------------------------------------------------------------------------

def lightning_decay(spec: ModelSpec) -> jax.Array:
    """[heads] float32: head h's constant decay exp(-2^(-8 h / heads)), h
    from 1 (the ALiBi slopes of Lightning Attention-2; the configuration
    states no scaling by layer, so there is none: another law is another
    table here and no program)."""
    n = spec.ssm_heads
    return jnp.exp(-(2.0 ** (-8.0 * jnp.arange(1, n + 1, dtype=jnp.float32)
                             / n)))


def _lightning_project(h: jax.Array, lp: dict, spec: ModelSpec,
                       positions: jax.Array):
    """(q, k, v [..., heads, d], z [..., inner]) of normed h [..., hidden]
    at ``positions`` [...]: q and k normalised a head and rotated."""
    n, d = spec.ssm_heads, spec.ssm_head_dim
    q, k, v, z = jnp.split(mm(h, lp["ssm_w_in"], "...h,hd->...d"), 4,
                           axis=-1)
    cos, sin = rope_tables(positions, d, spec.rope_theta)
    q = apply_rope(rms_norm(_split_heads(q, n, d), lp["ssm_q_norm"],
                            spec.rms_norm_eps), cos, sin)
    k = apply_rope(rms_norm(_split_heads(k, n, d), lp["ssm_k_norm"],
                            spec.rms_norm_eps), cos, sin)
    return q, k, _split_heads(v, n, d), z


def _lightning_terms(parts: tuple, spec: ModelSpec, live: jax.Array):
    """What the recurrence takes of (q, k, v, z), float32: dx = v (0 where
    ``live`` [...] is not), B = k, C = q / sqrt(d), the step (1 or 0) and
    its log-decay [..., heads]: the state of a row that is not live
    stands."""
    q, k, v, _ = parts
    on = live[..., None].astype(jnp.float32)
    step = jnp.broadcast_to(on, (*live.shape, spec.ssm_heads))
    return (v.astype(jnp.float32)[..., None, :] * on[..., None, None],
            k.astype(jnp.float32),
            q.astype(jnp.float32) * spec.ssm_head_dim ** -0.5,
            step, step * jnp.log(lightning_decay(spec)))


def _lightning_out(y: jax.Array, parts: tuple, lp: dict, spec: ModelSpec):
    """y [..., inner] float32 RMS-normalised a head, times the weight,
    times sigmoid(z), through W_out."""
    y = _rms_within(y, spec.ssm_heads, spec.rms_norm_eps) * lp["ssm_out_norm"]
    y = y * jax.nn.sigmoid(parts[3].astype(jnp.float32)).astype(jnp.bfloat16)
    return mm(y, lp["ssm_w_out"], "...d,dh->...h")


def lightning_step(h: jax.Array, lp: dict, spec: ModelSpec, state: jax.Array,
                   positions: jax.Array, live: jax.Array):
    """One token a row, XLA's. h [B, hidden] (normed), state [B, heads,
    head_dim, head_dim] float32, positions and live [B]. Returns (out [B,
    hidden], state); a row that is not live keeps its own."""
    parts = _lightning_project(h, lp, spec, positions)
    dx, bb, cc, _, da = _lightning_terms(parts, spec, live)
    y, state = state_update(state, jnp.exp(da), dx, bb, cc)
    return _lightning_out(y.reshape(h.shape[0], -1), parts, lp, spec), state


#: Tokens of a prompt chunk that the lightning recurrence computes at once:
#: its float32 terms (v, k, q, the decayed products of a chunk, what each
#: chunk adds to the state) are 16 KB a token and 8,192 tokens of them 1.3
#: GB of temporaries, where a v5e has 2 GB beside this model's weights, state
#: and pool (compiled for a described v5e, PR 45: 8 x 1,024 rows 2.4 GB
#: whole). Rows are independent and go a group at a time; one row's tokens
#: go a block at a time, the state carried from block to block.
LIGHTNING_BLOCK_TOKENS = 2048


def lightning_recurrence(parts: tuple, spec: ModelSpec, state: jax.Array,
                         valid: jax.Array,
                         limit: int = LIGHTNING_BLOCK_TOKENS):
    """The recurrence of (q, k, v [B, S, heads, d], z) over a chunk of a
    prompt a row, ``limit`` tokens at a time: (y [B, S, inner] float32, the
    state at each row's LAST REAL token). ``valid`` [B, S]."""
    q, k, v, _ = parts
    b, s = valid.shape

    def some(q, k, v, valid, state):
        return chunked_recurrence(
            *_lightning_terms((q, k, v, None), spec, valid), state,
            spec.ssm_chunk)

    if b * s <= limit:
        return some(q, k, v, valid, state)
    if b > 1:
        rows = max(1, limit // s)
        while b % rows:
            rows -= 1
        groups = lambda a: a.reshape(b // rows, rows, *a.shape[1:])  # noqa: E731
        y, state = jax.lax.map(
            lambda x: lightning_recurrence((*x[:3], None), spec, x[4], x[3],
                                           limit),
            tuple(groups(a) for a in (q, k, v, valid, state)))
        return y.reshape(b, s, -1), state.reshape(b, *state.shape[2:])
    # One row's tokens a block at a time, the state carried.
    size = limit
    while s % size:
        size //= 2
    blocks = lambda a: jnp.moveaxis(  # noqa: E731
        a.reshape(b, s // size, size, *a.shape[2:]), 1, 0)

    def block(state, x):
        y, state = some(*x, state)
        return state, y

    state, y = jax.lax.scan(block, state, tuple(
        blocks(a) for a in (q, k, v, valid)))
    return jnp.moveaxis(y, 0, 1).reshape(b, s, -1), state


# ---------------------------------------------------------------------------
# The gated delta-rule mixer
# ---------------------------------------------------------------------------

#: What is added under a key's or a query's sum of squares ahead of the
#: root (the l2 norm a head).
L2_EPS = 1e-6
#: Rows of a prompt batch whose chunk the delta rule solves at once: the
#: float32 decays between every pair of a chunk's tokens are ``chunk`` x
#: ``state`` x 4 bytes a token and head (1 MB a token at 64 heads, chunks
#: of 32 and keys of 128: 34 MB a row and chunk), and further rows go in
#: turns (``jax.lax.map``).
DELTA_ROWS = 8


def _delta_project(h: jax.Array, lp: dict, spec: ModelSpec):
    """Everything that reads a matrix ahead of the state: (q | k | v [...,
    channels] as the convolution takes them, the decay's f [..., heads x
    state], beta's logit [..., heads], the output gate's logit [...,
    inner]) of normed h [..., hidden]."""
    qkv = mm(h, lp["ssm_w_in"], "...h,hd->...d")
    with scope("ssm.gates"):
        f = mm(mm(h, lp["ssm_w_fa"], "...h,hr->...r"), lp["ssm_w_fb"],
               "...r,rd->...d")
        gate = mm(mm(h, lp["ssm_w_ga"], "...h,hr->...r"), lp["ssm_w_gb"],
                  "...r,rd->...d")
        return qkv, f, mm(h, lp["ssm_w_beta"], "...h,hd->...d"), gate


def _delta_qkv(x: jax.Array, spec: ModelSpec):
    """The convolution's output x [..., channels] float32 (before its
    SiLU) as (q, k [..., heads, state], v [..., heads, head_dim]) float32:
    q and k unit vectors a head, q over sqrt(state)."""
    n, dk, dv = spec.ssm_heads, spec.ssm_state, spec.ssm_head_dim
    q, k, v = jnp.split(jax.nn.silu(x), [n * dk, 2 * n * dk], axis=-1)
    unit = lambda a: a * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)
    q = unit(q.reshape(*q.shape[:-1], n, dk)) * dk ** -0.5
    return q, unit(k.reshape(*k.shape[:-1], n, dk)), v.reshape(
        *v.shape[:-1], n, dv)


def _delta_gates(f: jax.Array, beta: jax.Array, lp: dict, spec: ModelSpec,
                 live: jax.Array):
    """(g [..., heads, state] float32: a channel's log-decay ``-exp(A_log)
    softplus(f + dt_bias)``, beta [..., heads] float32: ``ssm_beta_scale x
    sigmoid``), both 0 where ``live`` [...] is not: the state stands."""
    with scope("ssm.gates"):
        n, dk = spec.ssm_heads, spec.ssm_state
        rate = jnp.exp(lp["ssm_a_log"][0].astype(jnp.float32))     # [heads]
        g = jax.nn.softplus(f.astype(jnp.float32)
                            + lp["ssm_dt_bias"][:, 0].astype(jnp.float32))
        g = -rate[:, None] * g.reshape(*g.shape[:-1], n, dk)
        beta = spec.ssm_beta_scale * jax.nn.sigmoid(beta.astype(jnp.float32))
        return (jnp.where(live[..., None, None], g, 0.0),
                jnp.where(live[..., None], beta, 0.0))


def _delta_out(y: jax.Array, parts: tuple, lp: dict, spec: ModelSpec):
    """y [..., inner] float32 RMS-normalised a head, times the weight (one
    of head_dim for every head), times sigmoid of the gate, through W_out."""
    n = spec.ssm_heads
    y = _rms_within(y, n, spec.rms_norm_eps)
    y = (y.reshape(*y.shape[:-1], n, -1) * lp["ssm_out_norm"]).reshape(y.shape)
    with scope("ssm.gates"):
        y = y * jax.nn.sigmoid(parts[3].astype(jnp.float32)).astype(
            jnp.bfloat16)
    return mm(y, lp["ssm_w_out"], "...d,dh->...h")


def _delta_token_of(parts: tuple, lp: dict, spec: ModelSpec, acc: jax.Array,
                    live: jax.Array):
    """What one token a row hands the delta rule: (q, k [B, heads, state],
    v [B, heads, head_dim], g [B, heads, state], beta [B, heads], all
    float32) of its projections ``parts`` (``_delta_project``) and the
    convolution's sum ``acc`` [B, C] float32 (``conv_token``)."""
    _, f, beta, _ = parts
    with scope("ssm.conv"):
        q, k, v = _delta_qkv(acc, spec)
    return (q, k, v, *_delta_gates(f, beta, lp, spec, live))


def delta_update(state: jax.Array, q: jax.Array, k: jax.Array, v: jax.Array,
                 g: jax.Array, beta: jax.Array):
    """One token of the gated delta rule over every row, XLA's: state [B,
    heads, head_dim (v), state (k)] float32, q, k and g [B, heads, state],
    v [B, heads, head_dim], beta [B, heads]: ``S' = S Diag(exp g)`` (a
    channel of the k axis its own decay), ``S <- S' + beta (v - S' k) (x)
    k``; returns (``S q`` [B, heads, head_dim], S). Where g and beta are 0
    the state stands bit for bit. What the kernel of engine/recurrence.py
    (``delta_state_step``) is held to."""
    decayed = state * jnp.exp(g)[:, :, None, :]
    read = jnp.sum(decayed * k[:, :, None, :], axis=-1)
    d = beta[..., None] * (v - read)
    state = decayed + d[..., None] * k[:, :, None, :]
    return jnp.sum(state * q[:, :, None, :], axis=-1), state


def delta_step(h: jax.Array, lp: dict, spec: ModelSpec, state: jax.Array,
               conv: jax.Array, live: jax.Array):
    """One token a row, XLA's: the definition. h [B, hidden] (normed),
    state [B, heads, head_dim, state] float32, conv [K - 1, B, channels],
    live [B]. Returns (out [B, hidden], state, conv); a row that is not
    live keeps both."""
    parts = _delta_project(h, lp, spec)
    with scope("ssm.conv"):
        acc, conv = conv_token(conv, parts[0], lp["ssm_conv_w"], live)
    q, k, v, g, beta = _delta_token_of(parts, lp, spec, acc, live)
    with scope("ssm.state"):
        y, state = delta_update(state, q, k, v, g, beta)
    return (_delta_out(y.reshape(h.shape[0], -1), parts, lp, spec), state,
            conv)


def delta_chunked(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                  beta: jax.Array, state: jax.Array, chunk: int):
    """The gated delta rule over a chunk of a prompt a row, ``chunk`` tokens
    at a time. q, k and g [B, S, heads, state], v [B, S, heads, head_dim],
    beta [B, S, heads] (g and beta 0 past a row's last real token: the
    state stands there), all float32; ``state`` [B, heads, head_dim, state]
    what the rows hold as the chunk starts. Returns (y [B, S, heads x
    head_dim] float32, the state at each row's LAST REAL token).

    Within a chunk, with G_t the log-decay summed from the chunk's start
    through token t (a vector over the k axis), S_0 the state the chunk
    finds and w_t = beta_t (v_t - S'_t k_t) what token t writes along k_t:
    ``S_t = S_0 Diag(e^G_t) + sum_{s <= t} w_s (x) (k_s e^(G_t - G_s))``, so
    ``(I + tril(Diag(beta) A, -1)) W = Diag(beta) (V - (K e^G) S_0^T)`` with
    ``A_ts = sum_c k_tc k_sc e^(G_tc - G_sc)``: ONE triangular solve a head
    and chunk (forward substitution: with beta up to 2 the powers of the
    strict triangle grow and a doubling series loses the result), then
    ``y_t = (q_t e^G_t) S_0^T + sum_{s <= t} (q_t . k_s e^(G_t - G_s)) w_s``.
    Every exponent is a difference G_t - G_s with s <= t, taken a channel
    and a pair of tokens BEFORE the sum over channels, so none is positive:
    a channel may decay by e^-40 a token, and a product of e^(G_t) by
    e^(-G_s) would be 0 times infinity. That tensor is chunk x state floats
    a token and head, which is what keeps the chunk at 32 (config.
    SolarOpen2Spec.ssm_chunk); the sums over it are the VPU's, every
    product that remains float32 at the highest precision (they are a
    thousandth of the layer's projections)."""
    b, s, n, dk = k.shape
    if b > DELTA_ROWS:          # rows are independent: a group at a time
        y, state = jax.lax.map(
            lambda x: delta_chunked(*(a[None] for a in x), chunk),
            (q, k, v, g, beta, state), batch_size=DELTA_ROWS)
        return y[:, 0], state[:, 0]
    c = min(chunk, s)
    pad = -s % c
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad))
                                    + ((0, 0),) * (a.ndim - 2))
                            for a in (q, k, v, g, beta))
    nc = (s + pad) // c
    # [nc, B, heads, C, ...]: a chunk a step of the scan, a head's tokens
    # in rows.
    chunks = lambda a: jnp.moveaxis(  # noqa: E731
        a.reshape(b, nc, c, *a.shape[2:]), (1, 3), (0, 2))
    lower = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
    strict = jnp.arange(c)[:, None] > jnp.arange(c)[None, :]
    hi = jax.lax.Precision.HIGHEST

    def one(carried, x):
        q_c, k_c, v_c, g_c, beta_c = x
        cum = jnp.cumsum(g_c, axis=2)                       # [B,H,C,K]
        between = jnp.where(
            lower[:, :, None],
            jnp.exp(jnp.minimum(cum[:, :, :, None] - cum[:, :, None], 0.0)),
            0.0)                                            # [B,H,C,C,K]
        keys = between * k_c[:, :, None]
        kk = jnp.sum(keys * k_c[:, :, :, None], axis=-1)    # [B,H,C,C]
        qk = jnp.sum(keys * q_c[:, :, :, None], axis=-1)
        through = jnp.exp(cum)                              # [B,H,C,K]
        found = jnp.einsum("bhtk,bhvk->bhtv", k_c * through, carried,
                           precision=hi)
        system = (jnp.where(strict, beta_c[..., None] * kk, 0.0)
                  + jnp.eye(c, dtype=jnp.float32))
        w = jax.lax.linalg.triangular_solve(
            system, beta_c[..., None] * (v_c - found), left_side=True,
            lower=True, unit_diagonal=True)                 # [B,H,C,V]
        y = (jnp.einsum("bhtk,bhvk->bhtv", q_c * through, carried,
                        precision=hi)
             + jnp.einsum("bhts,bhsv->bhtv", qk, w, precision=hi))
        to_end = jnp.exp(cum[:, :, -1:] - cum)
        carried = (carried * through[:, :, -1][:, :, None, :]
                   + jnp.einsum("bhsv,bhsk->bhvk", w, k_c * to_end,
                                precision=hi))
        return carried, y

    state, y = jax.lax.scan(one, state, tuple(
        chunks(a) for a in (q, k, v, g, beta)))
    # [nc, B, heads, C, V] -> [B, S, heads x V]
    y = jnp.moveaxis(y, (0, 2), (1, 3)).reshape(b, nc * c, -1)
    return y[:, :s], state


def _delta_chunk(parts: tuple, lp: dict, spec: ModelSpec, state: jax.Array,
                 conv: jax.Array, valid: jax.Array, seq_lens: jax.Array):
    """A chunk of a prompt a row between its projections (``_delta_project``
    of h [B, S, hidden]): (y [B, S, inner] float32, state and conv at each
    row's LAST REAL token). ``state`` and ``conv`` what the rows hold as
    the chunk starts (conv [B, K - 1, channels], a ROW first), valid [B, S],
    seq_lens [B] the real tokens."""
    qkv, f, beta, _ = parts
    s, taps_n = qkv.shape[1], spec.ssm_conv
    with scope("ssm.conv"):
        full = jnp.concatenate([conv, qkv.astype(conv.dtype)], axis=1)
        taps = lp["ssm_conv_w"].astype(jnp.float32)
        acc = taps[0] * full[:, 0:s].astype(jnp.float32)
        for j in range(1, taps_n):
            acc = acc + taps[j] * full[:, j:j + s].astype(jnp.float32)
        q, k, v = _delta_qkv(acc, spec)
        # The last K - 1 real inputs: input t lies at t + K - 1 of ``full``.
        last = seq_lens[:, None] + jnp.arange(taps_n - 1)[None, :]
        conv = jnp.take_along_axis(full, last[:, :, None], axis=1)
    g, beta = _delta_gates(f, beta, lp, spec, valid)
    with scope("ssm.chunk"):
        y, state = delta_chunked(q, k, v, g, beta, state, spec.ssm_chunk)
    return y, state, conv


def delta_prefill(h: jax.Array, lp: dict, spec: ModelSpec, state: jax.Array,
                  conv: jax.Array, valid: jax.Array, seq_lens: jax.Array):
    """A chunk of a prompt a row. h [B, S, hidden] (normed), ``state`` and
    ``conv`` what the rows hold as the chunk starts (zeros at position 0).
    Returns (out [B, S, hidden], state and conv at each row's LAST REAL
    token)."""
    parts = _delta_project(h, lp, spec)
    y, state, conv = _delta_chunk(parts, lp, spec, state, conv, valid,
                                  seq_lens)
    return _delta_out(y, parts, lp, spec), state, conv


# ---------------------------------------------------------------------------
# Attention over chosen blocks of keys
# ---------------------------------------------------------------------------

def stripe_means(k: jax.Array, stride: int) -> jax.Array:
    """k [B, S, Nkv, D] -> [B, S / stride, Nkv, D]: the mean of every
    ``stride`` keys in a row, as the compressed-key array holds it."""
    b, s, n, d = k.shape
    return jnp.mean(k.astype(jnp.float32).reshape(b, s // stride, stride, n,
                                                  d), axis=2).astype(k.dtype)


def choose_blocks(dots: jax.Array, n_keys: jax.Array, spec: ModelSpec):
    """The blocks a query keeps. dots [..., G, Hg, NS] float32: a head's
    query times stripe i of its KV group (unscaled; an entry is read only
    where the stripe's keys are all among the query's ``n_keys`` [...]: the
    keys at or before the query, itself among them). Returns (blocks
    [..., G, K] int32, kept [..., G, K] bool): the ``sparse_topk`` (K: all
    of them where the dots cover fewer) blocks of highest score, a block
    that does not exist yet not ``kept``; in rising order, the ones that do
    not exist last."""
    st, bk = spec.sparse_stride, spec.sparse_block
    per = bk // st
    ns = dots.shape[-1]
    nb = ns // per
    n = n_keys[..., None, None]
    # Compressed key j is the mean of stripes j and j + 1.
    score = 0.5 * (dots[..., :-1] + dots[..., 1:]) * spec.head_dim ** -0.5
    whole = (jnp.arange(ns - 1) + 2) * st <= n[..., None]
    top = jnp.max(jnp.where(whole, score, -jnp.inf), axis=-1, keepdims=True)
    e = jnp.where(whole, jnp.exp(score - jnp.where(jnp.isfinite(top), top,
                                                   0.0)), 0.0)
    p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    group = jnp.where(whole[..., 0, :], jnp.sum(p, axis=-2), -jnp.inf)
    # A block's score: the largest over the compressed keys that overlap
    # it, j = per * b - 1 to per * b + per - 1.
    lead = group.shape[:-1]
    edge = jnp.full((*lead, 1), -jnp.inf)
    padded = jnp.concatenate([edge, group, edge], axis=-1)
    block = jnp.maximum(
        jnp.max(padded[..., 1:].reshape(*lead, nb, per), axis=-1),
        padded[..., 0:nb * per:per])
    ids = jnp.arange(nb)
    own = (n - 1) // bk                                        # [..., 1, 1]
    exists = ids <= own
    forced = ((ids < spec.sparse_init_blocks)
              | (own - ids < spec.sparse_window // bk))
    block = jnp.where(forced, jnp.inf, block)
    block = jnp.where(exists, block, -jnp.inf)
    # The set lax.top_k keeps, by counts and no sort: block i stays when
    # fewer than K blocks beat it, where j beats i by a higher score, or by
    # an equal one and a lower index (top_k's order: equal scores keep the
    # lower block); a block that does not exist (-inf, and past every one
    # that does) so ranks behind them all, whatever they score. The ranks
    # are a permutation, so K stay, never more; they come out in RISING
    # order: place k holds the block that k kept ones lie below, which is
    # the count of blocks with at most k kept up to them.
    keep = min(spec.sparse_topk, nb)
    mine, other = block[..., :, None], block[..., None, :]
    beats = (other > mine) | ((other == mine) & (ids[None, :] < ids[:, None]))
    stays = jnp.sum(beats, axis=-1, dtype=jnp.int32) < keep     # [..., G, nb]
    upto = jnp.cumsum(stays, axis=-1, dtype=jnp.int32)
    blocks = jnp.sum(upto[..., None, :] <= jnp.arange(keep)[:, None],
                     axis=-1, dtype=jnp.int32)                  # [..., G, K]
    return blocks, blocks <= own


def sparse_prefill_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                             positions: jax.Array, valid: jax.Array,
                             spec: ModelSpec, hist: tuple | None = None,
                             limit: int = LATENT_SCORE_BYTES):
    """A prefill chunk's attention over chosen blocks: dense scores under a
    block mask a query, ``limit`` bytes of float32 scores at a time (a
    chunk of queries). q [B,S,Nh,D], k and v [B,S,Nkv,D] the chunk's own,
    positions and valid [B,S]; ``hist`` None (the chunk starts at 0) or
    (k_hist, v_hist [Nkv,B,L,D], stripes [Nkv,B,L/stride,D], hist_lens
    [B]): the row's earlier pages and their stripes, history token l at
    position l. Returns (attn [B,S,Nh*D], the chunk's stripes [B,
    S/stride, Nkv, D] for the compressed-key array)."""
    b, s, nh, d = q.shape
    nkv = k.shape[2]
    st, bk = spec.sparse_stride, spec.sparse_block
    own = stripe_means(k, st)
    key_pos = positions[:, :1] + jnp.arange(s)[None, :]
    key_ok, keys, vals, stripes = valid, k, v, own
    if hist is not None:
        k_hist, v_hist, s_hist, hist_lens = hist
        old = k_hist.shape[2]
        keys = jnp.concatenate([jnp.moveaxis(k_hist, 0, 2), k], axis=1)
        vals = jnp.concatenate([jnp.moveaxis(v_hist, 0, 2), v], axis=1)
        key_pos = jnp.concatenate(
            [jnp.broadcast_to(jnp.arange(old)[None, :], (b, old)), key_pos],
            axis=1)
        key_ok = jnp.concatenate(
            [jnp.arange(old)[None, :] < hist_lens[:, None], valid], axis=1)
        # Stripe i of the row stands at i: the history's up to where the
        # chunk starts, the chunk's own behind them.
        both = jnp.concatenate([jnp.moveaxis(s_hist, 0, 2), own], axis=1)
        first = positions[:, :1] // st
        at = jnp.arange(both.shape[1])[None, :]
        source = jnp.where(at < first, at,
                           jnp.minimum(old // st + at - first,
                                       both.shape[1] - 1))
        stripes = jnp.take_along_axis(both, source[:, :, None, None], axis=1)
    total = keys.shape[1]
    nb = total // bk
    # Key k of the row lies in block ``block_of[k // bk]``: whole blocks of
    # the history's bucket, then of the chunk from where it starts (a page's
    # border, so a block's). A query's mask over blocks is gathered a BLOCK
    # and repeated over its keys: gathered a key it is a billion gathered
    # elements a prompt of 8,192 (2 s of a v5e: my chip run, PR 45, call 1).
    block_of = key_pos[:, ::bk] // bk                          # [B, nb]
    per = s
    while per > 16 and per % 2 == 0 and 4 * b * nh * per * total > limit:
        per //= 2
    qg = q.reshape(b, s // per, per, nkv, nh // nkv, d)

    def some(x):
        qc, qpos = x                     # [B, per, Nkv, G, D], [B, per]
        with scope("attn.index"):
            dots = jnp.einsum("bqngd,bind->bqngi", qc, stripes,
                              preferred_element_type=jnp.float32)
            blocks, kept = choose_blocks(dots, qpos + 1, spec)
            chosen = jnp.any((blocks[..., None] == jnp.arange(nb))
                             & kept[..., None], axis=-2)       # [B,per,Nkv,nb]
        with scope("attn.core"):
            seen = jnp.repeat(jnp.take_along_axis(
                chosen, jnp.broadcast_to(block_of[:, None, None, :],
                                         (b, per, nkv, nb)), axis=-1),
                bk, axis=-1)
            seen = (seen & key_ok[:, None, None, :]
                    & (key_pos[:, None, None, :] <= qpos[:, :, None, None]))
            scores = jnp.einsum("bqngd,bknd->bqngk", qc, keys,
                                preferred_element_type=jnp.float32) * d ** -0.5
            scores = jnp.where(seen[:, :, :, None, :], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
            return jnp.einsum("bqngk,bknd->bqngd", probs, vals)

    pos = positions.reshape(b, s // per, per)
    out = jax.lax.map(some, (jnp.moveaxis(qg, 1, 0), jnp.moveaxis(pos, 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, nh * d), own


def pool_stripes(comp: jax.Array, layer: jax.Array, page_table: jax.Array,
                 spec: ModelSpec) -> jax.Array:
    """A row's stripes out of the compressed-key array (``spec.
    comp_key_shape``) by its page table [B, maxP]: [Nkv, B, maxP * stripes
    a page, D]; stripe i of the row stands at i. XLA's gather of every
    slot's whole bucket: a prefill chunk's history, and the decode step's
    wherever attention.stripe_scores_pallas does not run."""
    nkv, b = comp.shape[1], page_table.shape[0]
    index = (jnp.broadcast_to(layer, (nkv, *page_table.shape)),
             jnp.arange(nkv)[:, None, None],
             jnp.broadcast_to(page_table[None], (nkv, *page_table.shape)))
    return comp[index].reshape(nkv, b, -1, spec.head_dim)


def window_stripes(k_cache: jax.Array, layer: jax.Array,
                   page_table: jax.Array, hist_lens: jax.Array,
                   k_win: jax.Array, m: jax.Array,
                   k_self: jax.Array | None, spec: ModelSpec):
    """The stripes that a window's own tokens complete, which the
    compressed-key array does not hold until the window commits: (means
    [B, W, Nkv, D] bfloat16, first [B] int32): candidate c is stripe
    ``first + c`` of the row, the mean of its keys among the pool's last
    (positions from first * stride up to ``hist_lens``), the window's
    earlier tokens k_win [Nkv, B, M, D] (columns under ``m``, a scalar or
    [B]; column j at position hist_lens + j) and the step's own key k_self
    [B, Nkv, D] (at hist_lens + m; None: none). A candidate is whole once
    (first + c + 1) * stride keys are among the query's; until then its
    mean is of the keys so far and nobody reads it."""
    st = spec.sparse_stride
    page = k_cache.shape[3]
    nkv, b, M, _ = k_win.shape
    m = jnp.broadcast_to(m, (b,))
    first = hist_lens // st
    # The pool's share of stripe ``first``: its page's rows from the
    # stripe's start.
    at = first * st
    pages = jnp.take_along_axis(
        page_table, jnp.clip(at // page, 0, page_table.shape[1] - 1)[:, None],
        axis=1)                                                # [B, 1]
    rows = (at % page)[:, None] + jnp.arange(st)[None, :]      # [B, st]
    tail = k_cache[jnp.broadcast_to(layer, (b, st, nkv)),
                   jnp.arange(nkv)[None, None, :],
                   pages[:, :, None], rows[:, :, None]]        # [B,st,Nkv,D]
    recent = [tail, jnp.moveaxis(k_win, 0, 2)]
    position = [at[:, None] + jnp.arange(st)[None, :],
                hist_lens[:, None] + jnp.arange(M)[None, :]]
    there = [position[0] < hist_lens[:, None],
             jnp.arange(M)[None, :] < m[:, None]]
    if k_self is not None:
        recent.append(k_self[:, None])
        position.append((hist_lens + m)[:, None])
        there.append(jnp.ones((b, 1), bool))
    recent, position, there = (jnp.concatenate(a, axis=1)
                               for a in (recent, position, there))
    cands = (M + st - 1) // st + 1
    member = (there[:, None, :]
              & (position[:, None, :] // st
                 == first[:, None, None] + jnp.arange(cands)[None, :, None]))
    means = jnp.einsum("bcr,brnd->bcnd", member.astype(jnp.float32),
                       recent.astype(jnp.float32)) / st
    return means.astype(k_win.dtype), first


def sparse_window_attention(q: jax.Array, k_cache: jax.Array,
                            v_cache: jax.Array, comp: jax.Array,
                            layer: jax.Array, page_table: jax.Array,
                            hist_lens: jax.Array, k_win: jax.Array,
                            v_win: jax.Array, m: jax.Array,
                            k_self: jax.Array, v_self: jax.Array,
                            spec: ModelSpec, live: jax.Array,
                            backends: Backends = XLA):
    """Decode attention over chosen blocks for step ``m`` of a window (the
    operands of model.paged_window_attention_xla, and ``comp`` the
    compressed-key array): scores over the row's stripes, the choice, and
    the pool's reader over the chosen blocks alone. The window's own
    tokens and the step's lie in the blocks the query's window keeps.
    Who scores the stripes the pool holds (``backends.stripe_scorer``):
    where the Pallas reader runs, the kernel that walks a LIVE row's pages
    of the array once; else XLA's gather of every slot's bucket
    (``pool_stripes``), scored. Under either a stripe is read only where it
    is whole in the pool of a live row, and under a ``where``: what the
    kernel did not write is undefined.
    Returns (out [B, Nh, D], counts [3] float32: the keys the live rows
    attended, a KV group's mean, the keys they had in context, and the keys
    whose stripes the choice READ: the live rows' own under the kernel,
    every slot's bucket under the gather)."""
    b, nh, d = q.shape
    nkv, pages, page = k_cache.shape[1], k_cache.shape[2], k_cache.shape[3]
    st, bk = spec.sparse_stride, spec.sparse_block
    group = nh // nkv
    M = k_win.shape[2]
    n_keys = hist_lens + m + 1
    qg = q.reshape(b, nkv, group, d)
    scorer = backends.stripe_scorer()
    with scope("attn.index"):
        # Stripe i of a live row is the pool's while i < pooled.
        pooled = jnp.where(live, hist_lens // st, 0)
        if scorer is None:
            old = jnp.einsum("bngd,nbid->bngi", qg,
                             pool_stripes(comp, layer, page_table, spec),
                             preferred_element_type=jnp.float32)
        else:
            old = scorer(qg, comp, layer, page_table, pooled * st, page)
        new, first = window_stripes(k_cache, layer, page_table, hist_lens,
                                    k_win, m, k_self, spec)
        new = jnp.einsum("bngd,bcnd->bngc", qg, new,
                         preferred_element_type=jnp.float32)
        at = jnp.arange(old.shape[-1])[None, :]                # [1, NS]
        dots = jnp.where((at < pooled[:, None])[:, None, None, :], old, 0.0)
        for c in range(new.shape[-1]):
            dots = dots + jnp.where(
                (at == first[:, None] + c)[:, None, None, :],
                new[..., c:c + 1], 0.0)
        # [B, Nkv, K], rising; a block that does not exist past the pool's.
        blocks, kept = choose_blocks(dots, n_keys, spec)
        blocks = jnp.where(kept, blocks, dots.shape[-1] * st // bk)
        # The pool holds positions under hist_lens: whole blocks up to the
        # one of its last token, which the query's window keeps, and which
        # is the last of the table that the reader's length lets it read.
        last = jnp.maximum(hist_lens - 1, 0) // bk
        whole = jnp.sum(blocks < last[:, None, None], axis=-1)  # [B, Nkv]
        length = jnp.where(hist_lens[:, None] > 0,
                           whole * bk + (hist_lens - last * bk)[:, None], 0)
        # The pool as blocks of ONE KV head: block x of page p of head n
        # is block (n * pages + p) * blocks-a-page + x.
        per = page // bk
        of_page = jnp.take_along_axis(
            jnp.broadcast_to(page_table[:, None, :],
                             (b, nkv, page_table.shape[1])),
            jnp.clip(blocks // per, 0, page_table.shape[1] - 1), axis=-1)
        table = ((jnp.arange(nkv)[None, :, None] * pages + of_page) * per
                 + blocks % per)
    as_blocks = lambda c: c.reshape(c.shape[0], 1, nkv * pages * per,  # noqa: E731
                                    bk, d)
    rows = lambda a: a.reshape(b * nkv, *a.shape[2:])  # noqa: E731
    heads_first = lambda w: jnp.moveaxis(w, 0, 1).reshape(  # noqa: E731
        1, b * nkv, M, d)
    with scope("attn.core"):
        out = kv_attention(backends, window=True)(
            rows(qg), as_blocks(k_cache), as_blocks(v_cache), layer,
            rows(table).astype(jnp.int32), rows(length).astype(jnp.int32),
            heads_first(k_win), heads_first(v_win), m,
            rows(k_self)[:, None], rows(v_self)[:, None], group)
    attended = jnp.mean((length + m + 1).astype(jnp.float32), axis=-1)
    on = live.astype(jnp.float32)
    context = jnp.sum(n_keys.astype(jnp.float32) * on)
    read = context if scorer is not None else jnp.float32(
        b * page_table.shape[1] * page)
    return out.reshape(b, nh, d), jnp.stack(
        [jnp.sum(attended * on), context, read])


# ---------------------------------------------------------------------------
# The scan over groups
# ---------------------------------------------------------------------------

def _index(tree, i):
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, 0, keepdims=False), tree)


class Mixer(NamedTuple):
    """A recurrent mixer as ``scan_groups`` takes it, in three parts so
    that the state never rides a conditional: ``project(h, lp) -> parts``
    (the in-projections: what reads the weights), ``update(parts, lp,
    *state, p, on) -> (y, *state)`` (layer p of the state stacks updated
    in place; ``on`` a bool scalar or None: False in a group without a
    mixer, whose rows' state then stands), ``output(y, parts, lp) -> out``
    (gate, norm and out-projection)."""
    project: Callable
    update: Callable
    output: Callable


def in_layer(step):
    """``step(*rows) -> (y, *rows)`` over ONE layer's rows of the state
    stacks as ``Mixer.update`` takes them whole: layer p's rows sliced out
    and put back where they lay."""
    def update(*state_p):
        *state, p = state_p
        y, *new = step(*(_index(a, p) for a in state))
        return (y, *(jax.lax.dynamic_update_index_in_dim(a, n, p, 0)
                     for a, n in zip(state, new)))
    return update


def scan_groups(layers: dict, spec: ModelSpec, x: jax.Array, state: tuple,
                mixer: Mixer, attn_fn, kv_like: tuple, live=None,
                backends: Backends = XLA):
    """x through every group. ``state`` (the recurrent layers' stacks [M,
    rows, ...]) rides the carry and layer p rewrites its own rows in place
    (``mixer``); ``attn_fn(h, ap, a) -> (out, kv)`` for attention layer a
    of its stack (kv a tuple of arrays like ``kv_like``, ``_like``'s: what
    the layer leaves a token and what it counted); ``live`` and
    ``backends`` as model.ffn_block takes them: where x's rows take a
    kernel of engine/experts.py (model.expert_product) the expert stacks
    are not sliced a group but handed whole with the group's index, as
    model.scan_layers hands them (sliced ahead of a custom call a layer's
    experts are COPIED: 160 MB a matrix of 32 x 2,688 x 1,856). Returns (x,
    state, kv (each [A, ...]), the expert layers' load [E, n] or None)."""
    groups = groups_of(spec)
    eps, scale = spec.rms_norm_eps, spec.residual_scale
    norms = layers["mixer_norm"]
    ssm = {k: v for k, v in layers.items() if k.startswith("ssm_")}
    vectors = {k: v for k, v in ssm.items() if not k.startswith("ssm_w_")}
    ffn = {k: v for k, v in layers.items()
           if k.startswith(("moe_", "shared_")) or k in FFN_LEAVES}
    whole = {}
    if expert_product(math.prod(x.shape[:-1]), backends) != "masked":
        ffn, whole = whole_expert_leaves(ffn)
    attn = {k: layers[k] for k in ATTN_LEAVES if k in layers}
    # A stack with a sublayer a group is sliced by the scan; one that only
    # some groups have is indexed under their conditional.
    every = all(i >= 0 for i in groups.mixer_index)
    beside = spec.parallel_mixers   # every group: M and * on ONE normed input
    mixed = jnp.asarray([i >= 0 for i in groups.mixer_index])
    starred = jnp.asarray([a >= 0 for a in groups.attn_index])
    nth = lambda idx: jnp.asarray([max(i, 0) for i in idx])  # noqa: E731
    # The norm of sublayer i is row i of the stack; side by side the *
    # sublayers have none, and a row is a sublayer less those before it.
    at = lambda idx: norms[nth(  # noqa: E731
        [i - spec.layer_pattern[:max(i, 0)].count("*") for i in idx]
        if beside else idx)]
    add = (lambda x, out: x + out) if scale == 1.0 else (
        lambda x, out: x + out * scale)

    def group(carry, xs):
        x, *state = carry
        lp_m, lp_f, norm_m, norm_a, norm_f, has_m, star, a, p, g = xs
        if beside:
            # ONE norm in, ONE sum out (both drawn under ``ssm``); each
            # branch's muP constant sits on its own output (``_gated_out``,
            # ``_attn_out``).
            with scope("ssm"):
                h = rms_norm(x, norm_m, eps)
                parts = mixer.project(h, lp_m)
                y, *state = mixer.update(parts, lp_m, *state, p, None)
                out_s = mixer.output(y, parts, lp_m)
            out_a, kv = attn_fn(h, lp_m, p)
            with scope("ssm"):
                x = add(x, out_s + out_a)
            with scope("mlp"):
                x = add(x, ffn_block(rms_norm(x, norm_f, eps), lp_f, spec))
            return (x, *state), tuple(kv)
        with scope("ssm"):
            if every:
                h = rms_norm(x, norm_m, eps)
                parts = mixer.project(h, lp_m)
                y, *state = mixer.update(parts, lp_m, *state, p, None)
                x = add(x, mixer.output(y, parts, lp_m))
            elif ssm:
                # The matrices are indexed where they are multiplied; the
                # update takes the layer's vectors alone.
                project = lambda x: mixer.project(  # noqa: E731
                    rms_norm(x, norm_m, eps), _index(ssm, p))
                parts = jax.lax.cond(
                    has_m, project, lambda x: jax.tree.map(
                        lambda a: jnp.zeros(a.shape, a.dtype),
                        jax.eval_shape(project, x)), x)
                y, *state = mixer.update(parts, _index(vectors, p), *state,
                                         p, has_m)
                x = jax.lax.cond(
                    has_m, lambda x: add(x, mixer.output(
                        y, parts, _index(ssm, p))), lambda x: x, x)

        def attend(x):
            with scope("attn.qkv"):
                h = rms_norm(x, norm_a, eps)
            out, kv = attn_fn(h, _index(attn, a), a)
            return add(x, out), tuple(kv)

        def skip(x):
            return x, tuple(jnp.zeros(a.shape, a.dtype) for a in kv_like)

        x, kv = jax.lax.cond(star, attend, skip, x)
        with scope("mlp"):
            out = ffn_block(rms_norm(x, norm_f, eps),
                            {**lp_f, **layer_of(whole, g)}, spec, live=live,
                            backends=backends)
            out, load = out if isinstance(out, tuple) else (out, None)
            x = add(x, out)
        return (x, *state), (kv if load is None else (kv, load))

    n = len(groups.ffn_layer)
    (x, *state), out = jax.lax.scan(
        group, (x, *state),
        ({**ssm, **attn} if beside else ssm if every else None, ffn,
         at(groups.mixer_layer),
         at(groups.attn_layer), at(groups.ffn_layer), mixed, starred,
         nth(groups.attn_index),
         jnp.arange(n) if every else nth(groups.mixer_index),
         # The group's own index: its expert layer's in the stacks handed
         # whole (a group without a mixer shares ``p`` with its neighbour).
         jnp.arange(n)))
    kv, load = out if isinstance(out[0], tuple) else (out, None)
    held = jnp.asarray([p for p, a in enumerate(groups.attn_index) if a >= 0])
    return x, tuple(state), tuple(a[held] for a in kv), load


def _like(*shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _qkv(h: jax.Array, ap: dict, spec: ModelSpec,
         positions: jax.Array | None = None):
    """q [..., Nh, D], k and v [..., Nkv, D] of normed h; q and k
    RMS-normalised a head where the layer has the weights, and where the
    block's * layers rotate (``spec.attn_rope``) turned by ``positions``
    [...]: k times ``spec.key_multiplier`` AHEAD of the rotation, so that
    what a page holds is what is attended."""
    with scope("attn.qkv"):
        d = spec.head_dim
        h = times(h, spec.attn_in_multiplier)
        q = _split_heads(mm(h, ap["wq"], "...h,hd->...d"), spec.num_heads, d)
        k = _split_heads(mm(h, ap["wk"], "...h,hd->...d"),
                         spec.num_kv_heads, d)
        if "q_norm" in ap:
            q = rms_norm(q, ap["q_norm"], spec.rms_norm_eps)
            k = rms_norm(k, ap["k_norm"], spec.rms_norm_eps)
        k = times(k, spec.key_multiplier)
        if spec.attn_rope:
            cos, sin = rope_tables(positions, d, spec.rope_theta)
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        return q, k, _split_heads(mm(h, ap["wv"], "...h,hd->...d"),
                                  spec.num_kv_heads, d)


def _attn_out(attn: jax.Array, h: jax.Array, ap: dict, spec: ModelSpec):
    """attn [..., Nh * D] through W_o, gated by sigmoid(h W_z) where the
    layer has the gate."""
    with scope("attn.out"):
        if "wz" in ap:
            gate = jax.nn.sigmoid(mm(h, ap["wz"], "...h,hd->...d")
                                  .astype(jnp.float32))
            attn = attn * gate.astype(attn.dtype)
        return times(mm(attn, ap["wo"], "...d,dh->...h"),
                     spec.attn_out_multiplier)


def _embed(params: Params, spec: ModelSpec, tokens: jax.Array):
    with scope("embed"):
        x = embed_lookup(params["embed"], tokens)
        return x if spec.scale_emb == 1.0 else x * spec.scale_emb


#: The axis of the slot in each stack a scan carries (``split_state``): S [M,
#: slots, heads, head_dim, state]; the convolution's carried inputs [M, K -
#: 1, slots, channels], taps-major.
SLOT_AXIS = (1, 2)


def split_state(spec: ModelSpec, state: tuple) -> tuple:
    """The runner's state arrays as (what a scan carries: the recurrent
    layers' stacks by slot (``SLOT_AXIS``), the compressed-key array or
    None: the pool's third array, read where it lies and written at a
    commit)."""
    if spec.compressed_keys:
        return tuple(state[:-1]), state[-1]
    return tuple(state), None


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------

def prefill(params: Params, spec: ModelSpec, k_cache: jax.Array,
            v_cache: jax.Array, state: tuple, tokens: jax.Array,
            positions: jax.Array, page_table: jax.Array, seq_lens: jax.Array,
            slots: jax.Array, hist: tuple | None = None,
            backends: Backends = XLA):
    """model.prefill_forward for this block: a chunk of each row's prompt,
    whole (``hist`` None) or after earlier chunks (``hist`` (hist_table,
    hist_lens): the attention layers also read the row's earlier pages).
    ``state`` is the runner's state arrays (``split_state``), the
    recurrent layers' over ALL slots, carried through the scan and updated
    where they lie, and ``slots`` [B] the slot of each row (-1: none, the
    row's state goes nowhere): a row whose chunk starts at position 0
    starts from zeros, any other from its slot's state, and each leaves
    there the state at its last real token.
    ``backends``: see model.ffn_block (a window step's rows never take the
    grouped product: ``window_step`` hands its expert layers no record).
    Returns (last-token logits, k_cache, v_cache, state)."""
    b, s = tokens.shape
    page = k_cache.shape[3]
    x = _embed(params, spec, tokens)
    state, comp = split_state(spec, state)
    valid = jnp.arange(s)[None, :] < seq_lens[:, None]
    rows = jnp.clip(slots, 0, state[0].shape[1] - 1)
    fresh = positions[:, 0] == 0
    keep = (slots >= 0) & (seq_lens > 0)

    def live(on):
        return valid if on is None else valid & on

    def of_rows(step, on):
        """``step(*rows) -> (y, *rows)`` over the chunk's rows of ONE layer
        as ``Mixer.update`` takes the state stacks whole: each row's state
        read out of its slot, the ROW first ([B, ...]: a row's carried
        inputs [K - 1, channels] out of the taps-major stack; zeros where
        its chunk starts at position 0), and what it leaves written back
        there, a row a copy in place (a gather of every layer's rows ahead
        of the scan is 50 MB a row kept through it; a scatter of the batch
        may copy the arrays); nothing is written where ``on`` is False (a
        group without a mixer)."""
        kept = keep if on is None else keep & on
        def update(*state_p):
            *stacks, p = state_p
            y, *new = step(*(jnp.where(
                fresh.reshape(b, *(1,) * (a.ndim - 2)), 0,
                a[(p, *(slice(None),) * (axis - 1), rows)])
                for axis, a in zip(SLOT_AXIS, stacks)))
            out = []
            for axis, whole, rows_new in zip(SLOT_AXIS, stacks, new):
                for i in range(b):
                    at = ((p,) + (0,) * (axis - 1) + (rows[i],)
                          + (0,) * (whole.ndim - axis - 1))
                    old = jax.lax.dynamic_slice(
                        whole, at, (1, *whole.shape[1:axis], 1,
                                    *whole.shape[axis + 1:]))
                    whole = jax.lax.dynamic_update_slice(
                        whole, jnp.where(kept[i], jnp.expand_dims(
                            rows_new[i], (0, axis)), old), at)
                out.append(whole)
            return (y, *out)
        return update

    if spec.ssm_kind == "K":
        mixer = Mixer(
            lambda h, lp: _delta_project(h, lp, spec),
            lambda parts, lp, s_all, c_all, p, on: of_rows(
                lambda s_rows, c_rows: _delta_chunk(
                    parts, lp, spec, s_rows, c_rows, live(on),
                    seq_lens if on is None else jnp.where(on, seq_lens, 0)),
                on)(s_all, c_all, p),
            lambda y, parts, lp: _delta_out(y, parts, lp, spec))
    elif spec.ssm_conv:
        mixer = Mixer(
            lambda h, lp: _project(h, lp, spec),
            lambda parts, lp, s_all, c_all, p, on: of_rows(
                lambda s_rows, c_rows: _ssm_chunk(
                    parts, lp, spec, s_rows, c_rows, live(on),
                    seq_lens if on is None else jnp.where(on, seq_lens, 0)),
                on)(s_all, c_all, p),
            lambda y, parts, lp: _gated_out(y, parts[0], lp, spec))
    else:
        mixer = Mixer(
            lambda h, lp: _lightning_project(h, lp, spec, positions),
            lambda parts, lp, s_all, p, on: of_rows(
                lambda s_rows: lightning_recurrence(
                    parts, spec, s_rows, live(on)), on)(s_all, p),
            lambda y, parts, lp: _lightning_out(y, parts, lp, spec))

    def attn_fn(h, ap, a):
        q, k, v = _qkv(h, ap, spec, positions)
        old = ()
        if hist is not None:
            with scope("attn.kv_gather"):
                old = (gather_pages_folded(k_cache, a, hist[0]),
                       gather_pages_folded(v_cache, a, hist[0]))
        if comp is not None:
            if hist is not None:
                with scope("attn.index"):
                    old = (*old, pool_stripes(comp, a, hist[0], spec),
                           hist[1])
            attn, stripes = sparse_prefill_attention(
                q, k, v, positions, valid, spec, old or None)
            return _attn_out(attn, h, ap, spec), (k, v, stripes)
        with scope("attn.core"):
            if hist is None:
                attn = dense_causal_attention(
                    q, k, v, positions, valid, spec.q_per_kv).reshape(b, s, -1)
            else:
                attn = history_attention(q, k, v, *old, positions, valid,
                                         hist[1], spec)
        return _attn_out(attn, h, ap, spec), (k, v)

    nkv, d = spec.num_kv_heads, spec.head_dim
    kv_like = (_like(b, s, nkv, d),) * 2
    if comp is not None:
        kv_like += (_like(b, s // spec.sparse_stride, nkv, d),)
    x, state, (k_new, v_new, *stripes), _ = scan_groups(
        params["layers"], spec, x, state, mixer, attn_fn, kv_like,
        backends=backends)
    with scope("kv.commit"):
        n_attn = spec.pool_layers
        blocks = lambda a: (a.reshape(n_attn, b * (s // page), page, nkv, d)  # noqa: E731
                            .transpose(0, 3, 1, 2, 4))
        flat = page_table.reshape(-1)
        k_cache = scatter_pages(k_cache, blocks(k_new), flat)
        v_cache = scatter_pages(v_cache, blocks(v_new), flat)
    if comp is not None:
        with scope("attn.compress"):
            per = page // spec.sparse_stride
            comp = comp.at[:, :, flat].set(
                stripes[0].reshape(n_attn, b * (s // page), per, nkv, d)
                .transpose(0, 3, 1, 2, 4))
    with scope("lm_head"):
        x = rms_norm(x, params["final_norm"], spec.rms_norm_eps)
        last = jnp.maximum(seq_lens - 1, 0)
        x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        logits = lm_logits(x_last, params, spec)
    if comp is not None:
        state += (comp,)
    return logits, k_cache, v_cache, state


def window_step(params: Params, spec: ModelSpec, k_cache: jax.Array,
                v_cache: jax.Array, k_buf: jax.Array, v_buf: jax.Array,
                m: jax.Array, tokens: jax.Array, page_table: jax.Array,
                hist_lens: jax.Array, state: tuple, live: jax.Array,
                positions: jax.Array | None = None,
                comp: jax.Array | None = None, backends: Backends = XLA):
    """model.decode_window_step for this block: one token a slot, at
    ``positions`` [B] (a mixer that rotates reads them). The pool (its
    layers are the attention layers') and its compressed-key array
    ``comp`` are read-only and this window's
    earlier tokens come from k_buf / v_buf [A, Nkv, B, M, D]; ``state`` is
    the recurrent layers' arrays over all slots, carried through the
    window's steps: a row that is not ``live`` keeps its own.
    ``backends.ssm`` "kernel": the kernel of engine/recurrence.py updates
    the live slots' S where the stack lies; else XLA every slot's
    (``state_update``). Returns (logits, k_new and v_new [A, B, Nkv, D],
    state, counts: "moe" the expert layers' load [E, 5], "ssm" the live
    rows [1, 1], "attn" the keys attended, in context and read by the
    choice [A, 3]; the keys the host's table knows, runtime/flight.py
    COUNTS)."""
    b = tokens.shape[0]
    x = _embed(params, spec, tokens)
    attend = kv_attention(backends, window=True)
    with scope("ssm"):
        walk = live_walk(live)      # (XLA's path takes the count alone)
    kernel = backends.ssm == "kernel"

    def update(terms, s_all, p, on):
        """One token of layer p's rows: ``terms`` (dx [B,G,Hg,P], B and C
        [B,G,N], the decay [B, heads]) -> (y [B, inner], s_all). The live
        rows alone under the kernel, none where ``on`` is False."""
        dx, bb, cc, decay = terms
        with scope("ssm.state"):
            if kernel:
                s_all, y = state_step(
                    s_all, p, walk[0], visited(on), decay,
                    dx.reshape(b, decay.shape[1], -1), bb, cc,
                    interpret=backends.interpret)
            else:
                y, s_all = in_layer(lambda s_rows: state_update(
                    s_rows, decay, dx, bb, cc))(s_all, p)
        return y.reshape(b, -1), s_all

    def on_rows(on):
        return live if on is None else live & on

    def visited(on):
        return walk[1] if on is None else jnp.where(on, walk[1], 0)

    def convolve(c_all, p, on, new, taps):
        """One token of layer p's convolution: (its sum [B, C] float32,
        c_all with the live rows' carried inputs one token on)."""
        with scope("ssm.conv"):
            acc, conv = conv_token(_index(c_all, p), new, taps, on_rows(on))
            return acc, jax.lax.dynamic_update_index_in_dim(c_all, conv, p,
                                                            0)

    if spec.ssm_kind == "K":
        def delta(parts, lp, s_all, c_all, p, on):
            acc, c_all = convolve(c_all, p, on, parts[0], lp["ssm_conv_w"])
            q, k, v, g, beta = _delta_token_of(parts, lp, spec, acc,
                                               on_rows(on))
            with scope("ssm.state"):
                if kernel:
                    s_all, y = delta_state_step(
                        s_all, p, walk[0], visited(on), jnp.exp(g), k, q, v,
                        beta, interpret=backends.interpret)
                else:
                    y, s_all = in_layer(lambda s_rows: delta_update(
                        s_rows, q, k, v, g, beta))(s_all, p)
            return y.reshape(b, -1), s_all, c_all

        mixer = Mixer(lambda h, lp: _delta_project(h, lp, spec), delta,
                      lambda y, parts, lp: _delta_out(y, parts, lp, spec))
    elif spec.ssm_conv:
        def mamba(parts, lp, s_all, c_all, p, on):
            acc, c_all = convolve(c_all, p, on, parts[1], lp["ssm_conv_w"])
            x, bb, cc, dt, da = _token_of(parts, lp, spec, acc, on_rows(on))
            y, s_all = update((dt.reshape(*x.shape[:3], 1) * x, bb, cc,
                               jnp.exp(da)), s_all, p, on)
            y = y.reshape(x.shape) + _skip(lp, x)
            return y.reshape(b, -1), s_all, c_all

        mixer = Mixer(lambda h, lp: _project(h, lp, spec), mamba,
                      lambda y, parts, lp: _gated_out(y, parts[0], lp, spec))
    else:
        def lightning(parts, lp, s_all, p, on):
            dx, bb, cc, _, da = _lightning_terms(parts, spec, on_rows(on))
            return update((dx, bb, cc, jnp.exp(da)), s_all, p, on)

        mixer = Mixer(
            lambda h, lp: _lightning_project(h, lp, spec, positions),
            lightning,
            lambda y, parts, lp: _lightning_out(y, parts, lp, spec))

    def attn_fn(h, ap, a):
        q, k, v = _qkv(h, ap, spec, positions)
        at = (k_cache, v_cache, a, page_table, hist_lens, _index(k_buf, a),
              _index(v_buf, a), m, k, v)
        if comp is not None:
            attn, counts = sparse_window_attention(
                q, k_cache, v_cache, comp, *at[2:], spec, live, backends)
            return _attn_out(attn.reshape(b, -1), h, ap, spec), (k, v, counts)
        with scope("attn.core"):
            attn = attend(q, *at, spec.q_per_kv).reshape(b, -1)
        return _attn_out(attn, h, ap, spec), (k, v)

    kv_like = (_like(b, spec.num_kv_heads, spec.head_dim),) * 2
    if comp is not None:
        kv_like += (_like(3, dtype=jnp.float32),)
    x, state, (k_new, v_new, *counts), load = scan_groups(
        params["layers"], spec, x, state, mixer, attn_fn, kv_like, live=live,
        backends=backends)
    with scope("lm_head"):
        x = rms_norm(x, params["final_norm"], spec.rms_norm_eps)
        logits = lm_logits(x, params, spec)
    counted = {"ssm": walk[1].astype(jnp.float32).reshape(1, 1)}
    if load is not None:
        counted["moe"] = load
    if counts:
        counted["attn"] = counts[0].astype(jnp.float32)
    return logits, k_new, v_new, state, counted


def commit_stripes(comp: jax.Array, k_cache: jax.Array, k_buf: jax.Array,
                   page_table: jax.Array, hist_lens: jax.Array,
                   ends: jax.Array, spec: ModelSpec) -> jax.Array:
    """The compressed-key array after a window: every stripe that the
    window's tokens completed (``window_stripes`` over the pool as it was
    BEFORE the window's commit and the window's buffer k_buf [A, Nkv, B,
    M, D]) written where its page lies, a copy in place a row and
    candidate. ``hist_lens`` [B] the tokens the pool held as the window
    started, ``ends`` [B] those it holds after (a row that took no step:
    the same)."""
    st, page, d = spec.sparse_stride, k_cache.shape[3], spec.head_dim
    per = page // st
    n_attn, nkv, b, _, _ = k_buf.shape
    steps = ends - hist_lens                                   # [B]
    means = jax.lax.map(
        lambda a: window_stripes(k_cache, a, page_table, hist_lens, k_buf[a],
                                 steps, None, spec)[0],
        jnp.arange(n_attn))                                    # [A,B,W,Nkv,D]
    first = hist_lens // st
    cands = means.shape[2]

    def put(i, comp):
        row, c = i // cands, i % cands
        stripe = first[row] + c
        done = (steps[row] > 0) & ((stripe + 1) * st <= ends[row])
        of_page = page_table[row, jnp.clip(stripe * st // page, 0,
                                           page_table.shape[1] - 1)]
        # The page's stripes whole, [per, D] a layer and head: the array's
        # tiles as it lies (one stripe alone, and XLA turns the whole array
        # to put the layers minor, a copy in and one out a window: compiled
        # for a described v5e, PR 46).
        where = (0, 0, of_page, 0, 0)
        here = done & (jnp.arange(per) == (stripe * st % page) // st)
        new = jax.lax.dynamic_slice(
            means, (0, row, c, 0, 0), (n_attn, 1, 1, nkv, d))
        old = jax.lax.dynamic_slice(comp, where, (n_attn, nkv, 1, per, d))
        return jax.lax.dynamic_update_slice(
            comp, jnp.where(here[:, None], jnp.moveaxis(new, 3, 1), old),
            where)

    return jax.lax.fori_loop(0, b * cands, put, comp)
