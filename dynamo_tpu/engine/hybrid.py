"""The Nemotron-H block's programs: layers of ONE mixer each, three kinds.

``spec.layer_pattern`` gives every layer its kind, and a layer is
``h <- h + Mixer(RMS(h; w, eps))``:

- **M, Mamba-2.** ``[z | xBC | dt] = u W_in``; ``xBC_t = silu(b_c + sum_j
  w_c[j] xBC_{t-K+1+j})`` (causal, depthwise, K taps, zeros before the
  sequence); ``[x | B | C] = xBC``; ``dt_t = softplus(dt_t + dt_bias)``;
  ``A = -exp(A_log)``; for head h of group g, state S [head_dim, state]
  float32: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t[g]``,
  ``y_t = S_t C_t[g] + D[h] x_t``; ``y = y silu(z)``, RMS-normalised within
  each group, times a weight; ``out = y W_out``. A row keeps S and the last
  K - 1 inputs of the convolution, a layer (``spec.ssm_state_shapes``).
- **E, an expert layer:** model.ffn_block (sigmoid router with a selection
  bias, two-matrix relu2 experts, one shared expert of its own width); a
  prefill over model.MOE_DENSE_MAX_ROWS rows sends each row to its own
  experts (``scan_pairs`` hands the kernel of engine/experts.py the expert
  stacks whole), a window step multiplies every held expert.
- **\\*, attention:** grouped-query, causal, NO rotary embedding; K and V go
  to the pool, whose layers are these alone.

The pattern is pairs (M, E), some with a * between the two (``pairs_of``),
and every program here is ONE scan over the stacked pairs with the
attention layer under a ``lax.cond`` that indexes its own stack: 52 layers
unrolled, eight steps a window, do not compile in a set-up anyone waits
for. The recurrent state rides the scan's carry and each layer updates its
own rows in place; the pool is read-only inside a scan as in model.py.

Prefill computes the recurrence in chunks of ``spec.ssm_chunk`` tokens as
matrix products (``ssm_chunked``: within a chunk the decayed (C_l . B_s)
matrix times dt x, between chunks the state at each border); decode takes
one token (``ssm_step``). Past a row's last real token dt is 0 (the state
stands: exp(0) S + 0) and the convolution keeps the last real inputs, so a
padded batch, a dead slot and a frozen row leave a state exactly as it was.

Who updates the float32 state in a decode step (``Backends.ssm``, decided
by backends.choose as the pool's reader is, and beside it): where the
Pallas reader runs on one TPU device the kernel of engine/recurrence.py,
which is handed the stack over all layers where it lies and visits the
live slots of one layer (``ssm_step_live``: a dead slot is neither read nor
written, and the new state is read by C in the pass that writes it);
everywhere else (the CPU backend, any mesh, a runner asked for the XLA
reader) XLA through ``ssm_step``, over every slot of the layer's slice.
``ssm_step`` is the definition the kernel is held to. Prefill is XLA's
under either, and so is the convolution's state.
"""

from __future__ import annotations

import math
import re
from typing import NamedTuple

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.backends import XLA, Backends
from dynamo_tpu.engine.config import ModelSpec
from dynamo_tpu.engine.kv_quant import gather_pages_folded, scatter_pages
from dynamo_tpu.engine.model import (Params, _split_heads,
                                     dense_causal_attention, embed_lookup,
                                     expert_product, ffn_block,
                                     history_attention, kv_attention,
                                     layer_of, lm_logits, mm, rms_norm,
                                     whole_expert_leaves)
from dynamo_tpu.engine.perf import scope
from dynamo_tpu.engine.recurrence import state_step

ATTN_LEAVES = ("wq", "wk", "wv", "wo")


class Pairs(NamedTuple):
    """``spec.layer_pattern`` as its (M, E) pairs: for pair p the index
    among ALL layers of its M, of its E and of the * between them (-1:
    none), and that attention layer's index in its own stack."""
    mamba: tuple
    expert: tuple
    attn_layer: tuple
    attn_index: tuple


def pairs_of(spec: ModelSpec) -> Pairs:
    mamba, expert, attn_layer, attn_index = [], [], [], []
    seen = 0
    for found in re.finditer(r"M(\*?)E", spec.layer_pattern):
        star = bool(found.group(1))
        mamba.append(found.start())
        expert.append(found.end() - 1)
        attn_layer.append(found.start() + 1 if star else -1)
        attn_index.append(seen if star else -1)
        seen += star
    return Pairs(tuple(mamba), tuple(expert), tuple(attn_layer),
                 tuple(attn_index))


# ---------------------------------------------------------------------------
# The Mamba-2 mixer
# ---------------------------------------------------------------------------

def _project(h: jax.Array, lp: dict, spec: ModelSpec):
    """[z | xBC | dt] = h W_in (stored as z | xBC and dt:
    model._recurrent_shapes)."""
    zx = mm(h, lp["ssm_w_in"], "...h,hd->...d")
    z, xbc = jnp.split(zx, [spec.ssm_heads * spec.ssm_head_dim], axis=-1)
    return z, xbc, mm(h, lp["ssm_w_dt"], "...h,hd->...d")


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


def _gated_out(y: jax.Array, z: jax.Array, lp: dict, spec: ModelSpec):
    """y silu(z), RMS-normalised within each group, times the weight,
    through W_out. y [..., inner] float32."""
    y = y * jax.nn.silu(z.astype(jnp.float32))
    grouped = y.reshape(*y.shape[:-1], spec.ssm_groups, -1)
    var = jnp.mean(grouped * grouped, axis=-1, keepdims=True)
    grouped = grouped * jax.lax.rsqrt(var + spec.rms_norm_eps)
    y = grouped.reshape(y.shape).astype(jnp.bfloat16) * lp["ssm_gate_norm"]
    return mm(y, lp["ssm_w_out"], "...d,dh->...h")


def _token(h: jax.Array, lp: dict, spec: ModelSpec, conv: jax.Array,
           live: jax.Array):
    """What one token a row hands the recurrence: (z, x [B,G,Hg,P], B and C
    [B,G,N], dt and dt A [B, heads], all float32 but z; conv with the token
    behind its last inputs where the row is ``live``)."""
    z, xbc, dt_raw = _project(h, lp, spec)
    full = jnp.concatenate([conv, xbc[:, None].astype(conv.dtype)], axis=1)
    taps = lp["ssm_conv_w"].astype(jnp.float32)                # [K, C]
    xbc = jax.nn.silu(jnp.sum(full.astype(jnp.float32) * taps, axis=1)
                      + lp["ssm_conv_bias"][:, 0].astype(jnp.float32))
    conv = jnp.where(live[:, None, None], full[:, 1:], conv)
    return (z, *_split_xbc(xbc, spec), *_steps(dt_raw, lp, live), conv)


def _skip(lp: dict, x: jax.Array):
    """D x: what a token gives its own output past the state."""
    g, hg = x.shape[1], x.shape[2]
    return lp["ssm_d"][:, 0].astype(jnp.float32).reshape(g, hg, 1) * x


def ssm_step(h: jax.Array, lp: dict, spec: ModelSpec, state: jax.Array,
             conv: jax.Array, live: jax.Array):
    """One token a row. h [B, hidden] (normed), state [B, heads, head_dim,
    state] float32, conv [B, K - 1, channels], live [B]. Returns (out [B,
    hidden], state, conv); a row that is not live keeps both."""
    b = h.shape[0]
    z, x, bb, cc, dt, da, conv = _token(h, lp, spec, conv, live)
    g, hg = x.shape[1], x.shape[2]
    decay = jnp.exp(da).reshape(b, g, hg, 1, 1)
    dx = dt.reshape(b, g, hg, 1) * x                           # [B,G,Hg,P]
    grouped = state.reshape(b, g, hg, *state.shape[2:])
    grouped = decay * grouped + dx[..., None] * bb[:, :, None, None, :]
    y = jnp.sum(grouped * cc[:, :, None, None, :], axis=-1)    # [B,G,Hg,P]
    y = y + _skip(lp, x)
    return (_gated_out(y.reshape(b, -1), z, lp, spec),
            grouped.reshape(state.shape), conv)


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


def ssm_chunked(h: jax.Array, lp: dict, spec: ModelSpec, state: jax.Array,
                conv: jax.Array, valid: jax.Array, seq_lens: jax.Array):
    """A chunk of a prompt a row. h [B, S, hidden] (normed), ``state`` and
    ``conv`` what the rows hold as the chunk starts (zeros at position 0),
    valid [B, S], seq_lens [B] the real tokens. Returns (out [B, S,
    hidden], state and conv at each row's LAST REAL token)."""
    b, s, _ = h.shape
    taps_n = spec.ssm_conv
    z, xbc, dt_raw = _project(h, lp, spec)
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
    q = min(spec.ssm_chunk, s)
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
    y = y + lp["ssm_d"][:, 0].astype(jnp.float32).reshape(g, hg, 1) * x
    y = y.reshape(b, nc * q, -1)[:, :s]
    return (_gated_out(y, z, lp, spec), grouped.reshape(state.shape), conv)


# ---------------------------------------------------------------------------
# The scan over pairs
# ---------------------------------------------------------------------------

def _index(tree, i):
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, 0, keepdims=False), tree)


def in_layer(step):
    """``step(h, lp, S, conv) -> (out, S, conv)`` over ONE layer's rows as
    scan_pairs takes it: layer p's rows sliced out of the two stacks and
    put back where they lay."""
    def ssm_fn(h, lp, s_all, c_all, p):
        out, s_new, c_new = step(h, lp, _index(s_all, p), _index(c_all, p))
        return (out, jax.lax.dynamic_update_index_in_dim(s_all, s_new, p, 0),
                jax.lax.dynamic_update_index_in_dim(c_all, c_new, p, 0))
    return ssm_fn


def scan_pairs(layers: dict, spec: ModelSpec, x: jax.Array, state: tuple,
               ssm_fn, attn_fn, kv_like: tuple, live=None,
               backends: Backends = XLA):
    """x through every layer. ``state`` (S [M, rows, ...], conv [M, rows,
    ...]) rides the carry and layer p rewrites its own rows in place:
    ``ssm_fn(h, lp, S, conv, p) -> (out, S, conv)`` over the whole stacks
    (``in_layer`` for a step over one layer's rows); ``attn_fn(h, ap, a) ->
    (out, k, v)`` for attention layer a of its stack (k and v shaped as
    ``kv_like``); ``live`` and ``backends`` as model.ffn_block takes
    them: where x's rows take the grouped product the expert stacks are not
    sliced a pair but handed whole with the pair's index, as
    model.scan_layers hands them (sliced ahead of a custom call a layer's
    experts are COPIED: 160 MB a matrix of 32 x 2,688 x 1,856). Returns (x,
    state, k [A, ...], v [A, ...], the expert layers' load [E, n] or None)."""
    pairs = pairs_of(spec)
    eps = spec.rms_norm_eps
    norms = layers["mixer_norm"]
    ssm = {k: v for k, v in layers.items() if k.startswith("ssm_")}
    moe = {k: v for k, v in layers.items()
           if k.startswith(("moe_", "shared_"))}
    whole = {}
    if expert_product(math.prod(x.shape[:-1]), backends) == "grouped":
        moe, whole = whole_expert_leaves(moe)
    attn = {k: layers[k] for k in ATTN_LEAVES}
    starred = jnp.asarray([a >= 0 for a in pairs.attn_index])
    at = lambda idx: norms[jnp.asarray([max(i, 0) for i in idx])]  # noqa: E731

    def pair(carry, xs):
        x, s_all, c_all = carry
        lp_m, lp_e, norm_m, norm_a, norm_e, star, a, p = xs
        with scope("ssm"):
            out, s_all, c_all = ssm_fn(rms_norm(x, norm_m, eps), lp_m, s_all,
                                       c_all, p)
            x = x + out

        def attend(x):
            with scope("attn.qkv"):
                h = rms_norm(x, norm_a, eps)
            out, k, v = attn_fn(h, _index(attn, a), a)
            return x + out, k, v

        def skip(x):
            return x, *(jnp.zeros(shape, jnp.bfloat16) for shape in kv_like)

        x, k, v = jax.lax.cond(star, attend, skip, x)
        with scope("mlp"):
            out = ffn_block(rms_norm(x, norm_e, eps),
                            {**lp_e, **layer_of(whole, p)}, spec, live=live,
                            backends=backends)
            out, load = out if isinstance(out, tuple) else (out, None)
            x = x + out
        return (x, s_all, c_all), ((k, v) if load is None else (k, v, load))

    n = len(pairs.mamba)
    (x, *state), (k, v, *load) = jax.lax.scan(
        pair, (x, *state),
        (ssm, moe, at(pairs.mamba), at(pairs.attn_layer), at(pairs.expert),
         starred, jnp.asarray([max(a, 0) for a in pairs.attn_index]),
         jnp.arange(n)))
    held = jnp.asarray([p for p, a in enumerate(pairs.attn_index) if a >= 0])
    return x, tuple(state), k[held], v[held], (load[0] if load else None)


def _qkv(h: jax.Array, ap: dict, spec: ModelSpec):
    with scope("attn.qkv"):
        d = spec.head_dim
        return (_split_heads(mm(h, ap["wq"], "...h,hd->...d"),
                             spec.num_heads, d),
                _split_heads(mm(h, ap["wk"], "...h,hd->...d"),
                             spec.num_kv_heads, d),
                _split_heads(mm(h, ap["wv"], "...h,hd->...d"),
                             spec.num_kv_heads, d))


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
    ``state`` is the runner's two arrays over ALL slots and ``slots`` [B]
    the slot of each row (-1: none, the row's state goes nowhere): a row
    whose chunk starts at position 0 starts from zeros, any other from its
    slot's state, and each leaves there the state at its last real token.
    ``backends``: see model.ffn_block (a window step's rows never take the
    grouped product: ``window_step`` hands its expert layers no record).
    Returns (last-token logits, k_cache, v_cache, state)."""
    b, s = tokens.shape
    page = k_cache.shape[3]
    with scope("embed"):
        x = embed_lookup(params["embed"], tokens)
    valid = jnp.arange(s)[None, :] < seq_lens[:, None]
    rows = jnp.clip(slots, 0, state[0].shape[1] - 1)
    fresh = positions[:, 0] == 0
    with scope("ssm"):
        held = tuple(jnp.where(
            fresh.reshape(1, b, *(1,) * (a.ndim - 2)), 0, a[:, rows])
            for a in state)

    @in_layer
    def ssm_fn(h, lp, s_rows, c_rows):
        return ssm_chunked(h, lp, spec, s_rows, c_rows, valid, seq_lens)

    def attn_fn(h, ap, a):
        q, k, v = _qkv(h, ap, spec)
        if hist is None:
            with scope("attn.core"):
                attn = dense_causal_attention(
                    q, k, v, positions, valid, spec.q_per_kv).reshape(b, s, -1)
        else:
            with scope("attn.kv_gather"):
                k_hist = gather_pages_folded(k_cache, a, hist[0])
                v_hist = gather_pages_folded(v_cache, a, hist[0])
            with scope("attn.core"):
                attn = history_attention(q, k, v, k_hist, v_hist, positions,
                                         valid, hist[1], spec)
        with scope("attn.out"):
            return mm(attn, ap["wo"], "...d,dh->...h"), k, v

    nkv, d = spec.num_kv_heads, spec.head_dim
    x, held, k_new, v_new, _ = scan_pairs(
        params["layers"], spec, x, held, ssm_fn, attn_fn,
        ((b, s, nkv, d),) * 2, backends=backends)
    with scope("kv.commit"):
        n_attn = spec.pool_layers
        blocks = lambda a: (a.reshape(n_attn, b * (s // page), page, nkv, d)  # noqa: E731
                            .transpose(0, 3, 1, 2, 4))
        flat = page_table.reshape(-1)
        k_cache = scatter_pages(k_cache, blocks(k_new), flat)
        v_cache = scatter_pages(v_cache, blocks(v_new), flat)
    with scope("ssm"):
        # Each row's state into its slot, where the arrays lie (a scatter
        # of the whole batch may copy them: 49 MB a slot).
        keep = (slots >= 0) & (seq_lens > 0)
        out = []
        for whole, new in zip(state, held):
            for i in range(b):
                old = jax.lax.dynamic_slice_in_dim(whole, rows[i], 1, axis=1)
                whole = jax.lax.dynamic_update_slice_in_dim(
                    whole, jnp.where(keep[i], new[:, i:i + 1], old), rows[i],
                    axis=1)
            out.append(whole)
    with scope("lm_head"):
        x = rms_norm(x, params["final_norm"], spec.rms_norm_eps)
        last = jnp.maximum(seq_lens - 1, 0)
        x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        logits = lm_logits(x_last, params, spec)
    return logits, k_cache, v_cache, tuple(out)


def window_step(params: Params, spec: ModelSpec, k_cache: jax.Array,
                v_cache: jax.Array, k_buf: jax.Array, v_buf: jax.Array,
                m: jax.Array, tokens: jax.Array, page_table: jax.Array,
                hist_lens: jax.Array, state: tuple, live: jax.Array,
                backends: Backends = XLA):
    """model.decode_window_step for this block: one token a slot. The pool
    (its layers are the attention layers') is read-only and this window's
    earlier tokens come from k_buf / v_buf [A, Nkv, B, M, D]; ``state`` is
    the runner's two arrays over all slots, carried through the window's
    steps: a row that is not ``live`` keeps its own. ``backends.ssm``
    "kernel": the kernel of engine/recurrence.py updates the live slots' S
    where the stack lies; else XLA every slot's (``ssm_step``).
    Returns (logits, k_new and v_new [A, B, Nkv, D], state, counts: "moe"
    the expert layers' load [E, 5], "ssm" the live rows [1, 1]; the keys
    the host's table knows, runtime/flight.py COUNTS)."""
    b = tokens.shape[0]
    with scope("embed"):
        x = embed_lookup(params["embed"], tokens)
    attend = kv_attention(backends, window=True)
    with scope("ssm"):
        walk = live_walk(live)      # (XLA's path takes the count alone)

    if backends.ssm == "kernel":
        def ssm_fn(h, lp, s_all, c_all, p):
            out, s_all, c_new = ssm_step_live(
                h, lp, spec, s_all, p, _index(c_all, p), live, walk,
                interpret=backends.interpret)
            return out, s_all, jax.lax.dynamic_update_index_in_dim(
                c_all, c_new, p, 0)
    else:
        @in_layer
        def ssm_fn(h, lp, s_rows, c_rows):
            return ssm_step(h, lp, spec, s_rows, c_rows, live)

    def attn_fn(h, ap, a):
        q, k, v = _qkv(h, ap, spec)
        with scope("attn.core"):
            attn = attend(q, k_cache, v_cache, a, page_table, hist_lens,
                          _index(k_buf, a), _index(v_buf, a), m, k, v,
                          spec.q_per_kv).reshape(b, -1)
        with scope("attn.out"):
            return mm(attn, ap["wo"], "...d,dh->...h"), k, v

    x, state, k_new, v_new, load = scan_pairs(
        params["layers"], spec, x, state, ssm_fn, attn_fn,
        ((b, spec.num_kv_heads, spec.head_dim),) * 2, live=live)
    with scope("lm_head"):
        x = rms_norm(x, params["final_norm"], spec.rms_norm_eps)
        logits = lm_logits(x, params, spec)
    return (logits, k_new, v_new, state,
            {"moe": load, "ssm": walk[1].astype(jnp.float32).reshape(1, 1)})
