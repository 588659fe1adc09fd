"""Functional JAX transformer (the block kinds engine/config.py states) with
a paged KV cache.

Pure-functional, scan-over-layers (O(1) compile time in depth), bfloat16 on
the MXU with fp32 softmax/norm accumulations. Parameters and the KV cache are
sharded over a ("dp", "tp") mesh with XLA inserting the collectives
(all-reduce after attention-out and MLP-down projections) — the tpu-idiomatic
replacement for the reference engines' NCCL tensor parallelism (SURVEY.md
§2.7). RoPE uses HF's rotate-half convention so HF safetensors load directly
(interleaved pairs where a spec says so).
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dynamo_tpu.engine.backends import XLA, Backends
from dynamo_tpu.engine.config import DENSE_PREFIX, MTP_PREFIX, ModelSpec
from dynamo_tpu.engine.kv_quant import (gather_pages_folded, scatter_pages,
                                        scatter_tokens)
from dynamo_tpu.engine.perf import scope
from dynamo_tpu.engine.quant import QTensor

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# Weight application (bf16 or weight-only int8)
# ---------------------------------------------------------------------------

def mm(x: jax.Array, w, pattern: str) -> jax.Array:
    """einsum(x, w) where w may be a QTensor (int8 weight, per-out-channel
    scale): the int8 operand converts to bf16 inside the dot (XLA fuses
    the convert into the operand read — the dequantized matrix is never
    materialized) and the [out] scale multiplies the OUTPUT in f32."""
    if isinstance(w, QTensor):
        y = jnp.einsum(pattern, x, w.q.astype(jnp.bfloat16),
                       preferred_element_type=jnp.bfloat16)
        return (y.astype(jnp.float32) * w.s).astype(jnp.bfloat16)
    return jnp.einsum(pattern, x, w, preferred_element_type=jnp.bfloat16)


def times(x: jax.Array, constant) -> jax.Array:
    """x times one of a block's muP constants (a number, or a vector along
    x's last axis), in float32, as x's dtype; a constant of 1 is no
    operation at all, so a block without the constant keeps its program."""
    if isinstance(constant, (int, float)) and constant == 1:
        return x
    return (x.astype(jnp.float32) * constant).astype(x.dtype)


def lora_delta(x: jax.Array, ll: dict, ids: jax.Array) -> jax.Array:
    """Gathered batched low-rank correction ``x @ A[ids] @ B[ids]`` —
    the S-LoRA / Punica batched-heterogeneous-adapter step, as two
    gathered einsums so it lives INSIDE the same jit programs as the
    base projections (static shapes: adapter ids are data, not shape).

    x [B, H] or [B, T, H]; ll = one layer's stacks {"a": [S, H, r],
    "b": [S, r, D]}; ids [B] resident slot ids (0 = base model, whose
    stacks are all-zero — the correction is exact zeros and the output
    is bit-identical to the LoRA-free projection). The rank contraction
    accumulates in f32, matching mm()'s numerics discipline."""
    a = jnp.take(ll["a"], ids, axis=0)             # [B, H, r]
    b = jnp.take(ll["b"], ids, axis=0)             # [B, r, D]
    if x.ndim == 2:
        u = jnp.einsum("bh,bhr->br", x, a,
                       preferred_element_type=jnp.float32)
        return jnp.einsum("br,brd->bd", u.astype(jnp.bfloat16), b,
                          preferred_element_type=jnp.bfloat16)
    u = jnp.einsum("bth,bhr->btr", x, a,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("btr,brd->btd", u.astype(jnp.bfloat16), b,
                      preferred_element_type=jnp.bfloat16)


def qkv_lora(q, k, v, h, ll, ids):
    """Apply the wq/wk/wv corrections to freshly-projected q/k/v (h is
    the rms-normed layer input the projections read)."""
    q = q + lora_delta(h, ll["wq"], ids)
    k = k + lora_delta(h, ll["wk"], ids)
    v = v + lora_delta(h, ll["wv"], ids)
    return q, k, v


def embed_lookup(embed, tokens: jax.Array) -> jax.Array:
    """Token-embedding gather; int8 tables gather q rows and scale by the
    per-hidden-channel scale."""
    if isinstance(embed, QTensor):
        rows = embed.q[tokens].astype(jnp.float32) * embed.s[0]
        return rows.astype(jnp.bfloat16)
    return embed[tokens].astype(jnp.bfloat16)


def lm_logits(x: jax.Array, params: Params, spec: ModelSpec) -> jax.Array:
    """Final-hidden -> vocab logits (f32). Tied int8 embeddings contract
    over H, whose scale therefore folds into the activations; untied int8
    heads scale the output columns. ``spec.logit_divisor`` (muP) divides
    the hidden state first."""
    if spec.logit_divisor != 1.0:
        x = (x.astype(jnp.float32) / spec.logit_divisor).astype(x.dtype)
    if spec.tie_word_embeddings:
        w = params["embed"]
        if isinstance(w, QTensor):
            xs = (x.astype(jnp.float32) * w.s[0]).astype(jnp.bfloat16)
            return jnp.einsum("bh,vh->bv", xs, w.q.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)
        return jnp.einsum("bh,vh->bv", x, w,
                          preferred_element_type=jnp.float32)
    w = params.get("lm_head")
    if isinstance(w, QTensor):
        y = jnp.einsum("bh,hv->bv", x, w.q.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
        return y * w.s
    return jnp.einsum("bh,hv->bv", x, w, preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Parameter init + sharding specs
# ---------------------------------------------------------------------------

def _attention_shapes(spec: ModelSpec, L: int) -> dict:
    """The norms and attention leaves of L layers: q, k, v and o, or the
    DeepSeek-V3.2 block's latent projections and indexer. Its selection
    bias and the indexer's LayerNorm bias end in a 1 so that a generator
    drawing normal / sqrt(shape[-2]) draws them small beside what they are
    added to."""
    h, nh = spec.hidden_size, spec.num_heads
    if not spec.latent:
        d, nkv = spec.head_dim, spec.num_kv_heads
        return {
            "input_norm": (L, h),
            "post_attn_norm": (L, h),
            "wq": (L, h, nh * d),
            "wk": (L, h, nkv * d),
            "wv": (L, h, nkv * d),
            "wo": (L, nh * d, h),
        }
    r, qr = spec.kv_lora_rank, spec.q_lora_rank
    shapes = {
        "input_norm": (L, h),
        "post_attn_norm": (L, h),
        "wq_a": (L, h, qr),
        "q_a_norm": (L, qr),
        "wq_b": (L, qr, nh * spec.head_dim),
        "wkv_a": (L, h, r + spec.qk_rope_head_dim),
        "kv_a_norm": (L, r),
        # c Wkv_b, a head's key part and its value part as two leaves.
        "wk_b": (L, r, nh * spec.qk_nope_head_dim),
        "wv_b": (L, r, nh * spec.v_head_dim),
        "wo": (L, nh * spec.v_head_dim, h),
    }
    if spec.index_topk:     # the block WITH an indexer
        shapes.update({
            "index_wq_b": (L, qr, spec.index_n_heads * spec.index_head_dim),
            "index_wk": (L, h, spec.index_head_dim),
            "index_k_norm": (L, spec.index_head_dim),
            "index_k_bias": (L, spec.index_head_dim, 1),
            "index_w": (L, h, spec.index_n_heads),
        })
    return shapes


def _expert_shapes(spec: ModelSpec, L: int) -> dict:
    """The feed-forward leaves of L expert layers: the router over every
    expert of the deployment, the experts HELD, shared experts, the
    selection bias."""
    h = spec.hidden_size
    E, ie = spec.num_experts, spec.expert_size
    shapes = {"moe_gate": (L, h, spec.router_width),
              "moe_w_gate": (L, E, h, ie),
              "moe_w_up": (L, E, h, ie),
              "moe_w_down": (L, E, ie, h)}
    if spec.num_shared_experts:
        S = spec.num_shared_experts
        si = spec.shared_intermediate_size or ie
        shapes.update({"shared_w_gate": (L, S, h, si),
                       "shared_w_up": (L, S, h, si),
                       "shared_w_down": (L, S, si, h)})
    if spec.ffn_act == "relu2":     # two matrices an expert: no gate leaf
        shapes = {k: v for k, v in shapes.items() if "_w_gate" not in k}
    if spec.moe_select_bias:
        shapes["moe_bias"] = (L, spec.router_width, 1)
    return shapes


def _recurrent_shapes(spec: ModelSpec, L: int) -> dict:
    """The leaves of L Mamba-2 mixers (engine/hybrid.py has the equations).
    The in-projection W_in (z | xBC | dt) is stored as two leaves, the same
    numbers: z | xBC (a whole number of 128-lane tiles wide) and dt (one
    column a head); in one leaf of 10,304 columns the TPU's compiler copies
    the whole int8 stack into a padded layout every window (0.64 GB, 2.5
    ms: my chip run, PR 41, call 1).
    The convolution's taps lie [tap, channel] and the vectors of a head end
    in a 1, so that a generator drawing normal / sqrt(shape[-2]) draws taps
    of 1/2 (the convolution's output is of its input's size and the
    recurrence's term S C is a first-order part of y, beside D x) and small
    biases; the gated norm's weight is a ``_norm`` leaf (ones)."""
    h, nh = spec.hidden_size, spec.ssm_heads
    inner, chan = nh * spec.ssm_head_dim, spec.ssm_channels
    return {"ssm_w_in": (L, h, inner + chan),          # z | xBC
            "ssm_w_dt": (L, h, nh),
            "ssm_conv_w": (L, spec.ssm_conv, chan),
            "ssm_conv_bias": (L, chan, 1),
            "ssm_dt_bias": (L, nh, 1),
            "ssm_a_log": (L, nh, 1),
            "ssm_d": (L, nh, 1),
            "ssm_gate_norm": (L, inner),
            "ssm_w_out": (L, inner, h)}


def _lightning_shapes(spec: ModelSpec, L: int) -> dict:
    """The leaves of L lightning linear-attention mixers (engine/hybrid.py
    has the equations): ONE in-projection q | k | v | z (four times the
    inner width: whole lane tiles), the weights of the norms of a head's q
    and k and of the output (``_norm`` leaves: ones), the out-projection.
    The decay is no leaf: a head's constant by ``hybrid.lightning_decay``."""
    h, d = spec.hidden_size, spec.ssm_head_dim
    inner = spec.ssm_heads * d
    return {"ssm_w_in": (L, h, 4 * inner),
            "ssm_q_norm": (L, d), "ssm_k_norm": (L, d),
            "ssm_out_norm": (L, inner),
            "ssm_w_out": (L, inner, h)}


def _delta_shapes(spec: ModelSpec, L: int) -> dict:
    """The leaves of L gated delta-rule mixers (engine/hybrid.py has the
    equations): ONE in-projection q | k | v (whole lane tiles), the taps of
    the convolution over those channels [tap, channel] (no bias), the
    low-rank pairs behind the decay (``fa``, ``fb``) and the output gate
    (``ga``, ``gb``), beta's projection (a column a head), the
    out-projection; the output norm's weight a head (a ``_norm`` leaf:
    ones). ``ssm_dt_bias`` a channel ends in a 1 so that a generator drawing
    normal / sqrt(shape[-2]) draws it small; ``ssm_a_log`` a head lies [1,
    heads] so that the same law draws it of unit size: a head's decay rate
    exp(A_log) then spreads over a decade, some heads forget within a token
    and some keep a dozen (of unit size in EVERY head a state halves a
    token and the delta rule's correction reads nothing back:
    benchmark/references/solar_open2.py)."""
    h, nh, r = spec.hidden_size, spec.ssm_heads, spec.ssm_low_rank
    inner, chan = nh * spec.ssm_head_dim, spec.ssm_channels
    return {"ssm_w_in": (L, h, chan),                   # q | k | v
            "ssm_conv_w": (L, spec.ssm_conv, chan),
            "ssm_w_fa": (L, h, r), "ssm_w_fb": (L, r, nh * spec.ssm_state),
            "ssm_w_ga": (L, h, r), "ssm_w_gb": (L, r, inner),
            "ssm_w_beta": (L, h, nh),
            "ssm_a_log": (L, 1, nh),
            "ssm_dt_bias": (L, nh * spec.ssm_state, 1),
            "ssm_out_norm": (L, spec.ssm_head_dim),
            "ssm_w_out": (L, inner, h)}


def _pattern_shapes(spec: ModelSpec) -> dict:
    """``params["layers"]`` of a block whose sublayers are ONE mixer or
    feed-forward each (``spec.layer_pattern``, config.GROUP): a stack a
    kind, each over the sublayers of its kind in the model's order, and the
    norm ahead of every sublayer, stacked over all of them. An attention
    layer over chosen blocks (S) also has the norms of a head's q and k and
    its output gate ``wz``."""
    pattern = spec.layer_pattern
    attn = {k: v for k, v in _attention_shapes(spec, spec.pool_layers).items()
            if not k.endswith("_norm")}
    # A norm a sublayer; side by side (``spec.parallel_mixers``) M and *
    # share the mixer's, and the * sublayers have none.
    norms = len(pattern) - (pattern.count("*") if spec.parallel_mixers else 0)
    shapes = {"mixer_norm": (norms, spec.hidden_size)}
    if "M" in pattern:
        shapes.update(_recurrent_shapes(spec, pattern.count("M")))
    if "L" in pattern:
        shapes.update(_lightning_shapes(spec, pattern.count("L")))
    if "K" in pattern:
        shapes.update(_delta_shapes(spec, pattern.count("K")))
    if "E" in pattern:
        shapes.update(_expert_shapes(spec, pattern.count("E")))
    if "D" in pattern:
        n, h, i = pattern.count("D"), spec.hidden_size, spec.intermediate_size
        shapes.update({"w_gate": (n, h, i), "w_up": (n, h, i),
                       "w_down": (n, i, h)})
    shapes.update(attn)
    if "S" in pattern:
        n, d = pattern.count("S"), spec.head_dim
        shapes.update({"q_norm": (n, d), "k_norm": (n, d),
                       "wz": (n, spec.hidden_size, spec.num_heads * d)})
    if spec.attn_gate:      # a * layer gated by sigmoid(u W_z)
        shapes["wz"] = (pattern.count("*"), spec.hidden_size,
                        spec.num_heads * spec.head_dim)
    return shapes


def param_shapes(spec: ModelSpec) -> dict:
    h, d = spec.hidden_size, spec.head_dim
    nh, nkv = spec.num_heads, spec.num_kv_heads
    # Leading dense layers are leaves of their own (DENSE_PREFIX).
    L = spec.num_layers - spec.first_k_dense
    i = spec.intermediate_size
    layers = _attention_shapes(spec, L)
    if spec.parallel_block:             # one norm feeds both branches
        del layers["post_attn_norm"]
    if spec.sandwich_norm:
        # The gain of the norm of each sublayer's OUTPUT, a column: a
        # generator drawing normal / sqrt(shape[-2]) draws it small (a
        # 45th at 2,048 wide). Drawn as ones, as an INPUT norm's weight
        # is, every sublayer adds a vector of unit rms whatever it
        # computed, a stream of rms sqrt(k) after k of them grows by k to
        # the power of a half of the sublayer's gain squared in every pass
        # alike, and over 384 normed sublayers a rounding of 2 ** -9
        # parts the bfloat16 program from the float32 one by whole nats
        # (one seed in six on one v5e: PERF.md section 6, PR 48).
        layers["attn_out_gain"] = (L, h, 1)
        layers["mlp_out_gain"] = (L, h, 1)
    if spec.layer_pattern:
        layers = _pattern_shapes(spec)
    elif spec.num_experts:
        layers.update(_expert_shapes(spec, L))
    else:
        layers["w_gate"] = (L, h, i)
        layers["w_up"] = (L, h, i)
        layers["w_down"] = (L, i, h)
    if spec.first_k_dense:
        K = spec.first_k_dense
        dense = {**_attention_shapes(spec, K), "w_gate": (K, h, i),
                 "w_up": (K, h, i), "w_down": (K, i, h)}
        layers.update({DENSE_PREFIX + k: v for k, v in dense.items()})
    if spec.mtp_layers:
        # The prediction module: one expert layer of the block's kind (a
        # stack of one), the projection of [embedding ; hidden] and the
        # norms of its two inputs and of its output ahead of the model's
        # own head (MTP_PREFIX).
        M = spec.mtp_layers
        module = {**_attention_shapes(spec, M), **_expert_shapes(spec, M),
                  "w_eh": (M, 2 * h, h), "e_norm": (M, h), "h_norm": (M, h),
                  "head_norm": (M, h)}
        layers.update({MTP_PREFIX + k: v for k, v in module.items()})
    shapes = {
        "embed": (spec.vocab_size, h),
        "final_norm": (h,),
        "layers": layers,
    }
    if spec.qkv_bias:
        shapes["layers"]["bq"] = (L, nh * d)
        shapes["layers"]["bk"] = (L, nkv * d)
        shapes["layers"]["bv"] = (L, nkv * d)
    if not spec.tie_word_embeddings:
        shapes["lm_head"] = (h, spec.vocab_size)
    return shapes


def param_specs(spec: ModelSpec) -> dict:
    """PartitionSpecs: column-parallel qkv/gate/up, row-parallel o/down
    (Megatron layout — XLA adds the psum at row-parallel outputs). The
    stacked LAYER axis shards over "pp" (layer-sharded pipeline axis);
    MoE expert weights shard their EXPERT axis over "tp" (expert
    parallelism: each device computes its resident experts, XLA reduces
    the combine)."""
    layers: dict = {
        "input_norm": P("pp", None),
        "post_attn_norm": P("pp", None),
        "wq": P("pp", None, "tp"),
        "wk": P("pp", None, "tp"),
        "wv": P("pp", None, "tp"),
        "wo": P("pp", "tp", None),
    }
    if spec.latent:
        # Served on one device (config.block_refusals): nothing is split.
        layers = {k: P("pp", *([None] * (len(v) - 1)))
                  for k, v in _attention_shapes(spec, 1).items()}
    if spec.parallel_block:
        del layers["post_attn_norm"]
    if spec.sandwich_norm:
        layers["attn_out_gain"] = P("pp", None, None)
        layers["mlp_out_gain"] = P("pp", None, None)
    if spec.num_experts:
        layers["moe_gate"] = P("pp", None, None)
        layers["moe_w_gate"] = P("pp", "tp", None, None)
        layers["moe_w_up"] = P("pp", "tp", None, None)
        layers["moe_w_down"] = P("pp", "tp", None, None)
        if spec.num_shared_experts:
            # Every device computes every shared expert (such a block is
            # served on one device: config.block_refusals).
            for key in ("shared_w_gate", "shared_w_up", "shared_w_down"):
                layers[key] = P("pp", None, None, None)
        if spec.moe_select_bias:
            layers["moe_bias"] = P("pp", None, None)
    else:
        layers["w_gate"] = P("pp", None, "tp")
        layers["w_up"] = P("pp", None, "tp")
        layers["w_down"] = P("pp", "tp", None)
    if spec.first_k_dense:
        dense = {**{k: v for k, v in layers.items()
                    if k in _attention_shapes(spec, 1)},
                 "w_gate": P("pp", None, "tp"), "w_up": P("pp", None, "tp"),
                 "w_down": P("pp", "tp", None)}
        layers.update({DENSE_PREFIX + k: v for k, v in dense.items()})
    if spec.mtp_layers:
        # Served on one device (config.block_refusals): nothing is split.
        layers.update({k: P("pp", *([None] * (len(v) - 1)))
                       for k, v in param_shapes(spec)["layers"].items()
                       if k.startswith(MTP_PREFIX)})
    if spec.layer_pattern:
        # Served on one device (config.block_refusals): nothing is split.
        layers = {k: P(*([None] * len(v)))
                  for k, v in _pattern_shapes(spec).items()}
    specs = {
        "embed": P(None, "tp"),
        "final_norm": P(None),
        "layers": layers,
    }
    if spec.qkv_bias:
        specs["layers"]["bq"] = P("pp", "tp")
        specs["layers"]["bk"] = P("pp", "tp")
        specs["layers"]["bv"] = P("pp", "tp")
    if not spec.tie_word_embeddings:
        specs["lm_head"] = P(None, "tp")
    if spec.quant == "int8":
        # QTensor leaves mirror the weight spec; the scale keeps the
        # contraction axis (-2, size 1 in the scale) UNSHARDED — a 1-sized
        # axis can't shard over tp (wo/w_down are row-parallel there).
        from dynamo_tpu.engine.quant import QUANT_LAYER_KEYS

        def scale_spec(p: P) -> P:
            parts = list(p)
            parts[-2] = None
            return P(*parts)

        for key in QUANT_LAYER_KEYS:
            if key in specs["layers"]:
                p = specs["layers"][key]
                specs["layers"][key] = QTensor(q=p, s=scale_spec(p))
        specs["embed"] = QTensor(q=P(None, "tp"), s=P(None, "tp"))
        if not spec.tie_word_embeddings:
            specs["lm_head"] = QTensor(q=P(None, "tp"), s=P(None, "tp"))
    return specs


#: Rows up to which an expert layer whose experts are whole on one device
#: walks the held experts its live rows chose (experts.touched_product: ONE
#: custom call a layer, every row by each touched expert, read once as
#: stored); above it the (row, choice) pairs are sorted by held expert and
#: multiplied by their own experts only (experts.pairs_product, two calls a
#: layer). The product over every resident expert under the gate mask
#: ("masked") is left to a mesh, whose expert axis may be partitioned.
#: One layer INSIDE a scan over stacked layers on one v5e, int8 leaves, ms a
#: layer, masked | touched | grouped (PERF.md section 6, PR 56, calls 1 and
#: 2, scripts/expert_layer_bench.py --layers; [n]: held experts touched;
#: call 7 read the touched column at 32 (19) again on the tree as it is, ONE
#: product a tile and no loop over K-chunks: level to the third digit but
#: 4,096 x 1,280, 0.343 -> 0.367 here and 0.413 -> 0.375 alone, its cell level):
#:  rows (live), routing     64 of 2,560 x 768, 6 a row          16 held of 128 of 4,096 x 4,096, 8
#:   32 (19) random          0.515 | 0.450 | 0.652 [52]           1.193 | 0.848 | 1.163 [12]
#:   32 (19) balanced        0.512 | 0.511 | 0.738 [64]           1.190 | 1.124 | 1.530 [16]
#:   32 (32) random          0.514 | 0.496 | 0.725 [62]
#:   64 (64) random          0.518 | 0.514 | 0.774 [64]           1.183 | 1.128 [15]
#:  128 (128) random         0.638 | 0.534 | 0.815 [64]           1.448 | 1.169 [16]
#:  256 (256) random         1.202 | 1.020 | 0.908 [64]
#: and masked | touched at 32 (19) random [balanced], 64 and 128 rows:
#:  16 held of 256 of 7,168 x 2,048, 8 a row: 0.954 | 0.395 [0.945 | 1.001], 0.968 | 0.905, 1.250 | 1.038
#:  16 held of 64 of 2,048 x 1,536, 4 a row:  0.212 | 0.144 [0.209 | 0.211], 0.213 | 0.213, 0.281 | 0.227
#:  32 held of 128 two-matrix relu2 of 2,688 x 1,856, 6 a row (the up stack
#:  read as the chip holds it, experts.lies_turned):
#:                                            0.443 | 0.270 [0.439 | 0.411], 0.445 | 0.435, 0.581 | 0.464
#:  40 held of 320 of 4,096 x 1,280, 8 a row: 0.874 | 0.343 [0.865 | 0.413], 0.893 | 0.692, 1.138 | 0.914
#: The masked product streams the layer whatever was chosen (377 MB in 0.51
#: ms: 735 GB/s) and its work rides under that read to about 100 rows, then
#: grows with the rows. A visit of the walk costs its bytes at the same rate
#: (64 visits of 5.9 MB in 0.511 ms: 7.9 us, 740 GB/s; a tile's conversion
#: runs inside its product, experts._product) whatever the rows up to the
#: MXU's edge: it is level with the masked product where every held expert
#: is touched, ahead of it by what was not touched, and 16 to 25 % ahead at
#: 128 rows in all six geometries under either routing. ONE geometry loses
#: where ALL its experts are touched: 7,168 x 2,048 in tiles of 256 columns,
#: 1.00 against 0.95 ms at 32 rows and 0.96 at 64 (its cell touches 4 to 6 of
#: 16). The walk multiplies EVERY row by every touched expert, so past the
#: MXU's edge it pays twice (256 rows: 1.02) where the grouped product pays
#: a visit a (group, row tile) (0.91; at 128 rows 0.82 against 0.53): the
#: two cross between 128 and 256 rows, and the constant is 128. The grouped
#: product further up, ms a layer ALONE, masked | grouped at 512 | 1,024 |
#: 2,048 | 4,096 rows under the routing of random weights (PR 40, call 1;
#: the grouped column before PR 56 moved the conversion into its product,
#: which took 19 | 12 | 3.5 % off it at 256 | 1,024 | 4,096 rows: PR 56,
#: call 2): 2.65 | 1.14, 5.34 | 1.64, 11.2 | 3.23, 22.4 | 5.50 (64 of 2,560
#: x 768); 1.03 | 0.53, 2.05 | 0.69, 4.47 | 0.90, 8.36 | 2.24 (16 of 2,048 x
#: 1,536); 5.31 | 2.21, 10.8 | 3.25, 21.7 | 4.85, 43.3 | 8.06 (16 of 4,096
#: x 4,096); 1.98 | 0.92, 4.77 | 1.28, 8.97 | 2.58, 17.8 | 4.50 (32 of 2,688
#: x 1,856; PR 43, call 1).
MOE_DENSE_MAX_ROWS = 128

class LayerOf(NamedTuple):
    """A leaf of ``params["layers"]`` handed to a layer WHOLE: the stack
    over all layers and the layer's index (scan_layers ``whole_experts``)."""
    stack: Any   # [L, ...], or a QTensor of such
    layer: Any   # int32 scalar


#: The leaves a grouped expert layer reads through the kernel (a two-matrix
#: expert has no gate leaf: ``whole_expert_leaves`` takes those that are there).
EXPERT_LEAVES = ("moe_w_gate", "moe_w_up", "moe_w_down")


def whole_expert_leaves(layers: dict) -> tuple[dict, dict]:
    """``layers`` as (what a layer scan slices a layer, the EXPERT_LEAVES it
    has: stacks a scan closes over and hands each layer as ``LayerOf``)."""
    stacks = {k: layers[k] for k in EXPERT_LEAVES if k in layers}
    return {k: v for k, v in layers.items() if k not in stacks}, stacks


def layer_of(stacks: dict, layer) -> dict:
    """Those stacks as layer ``layer`` (int32 scalar) reads them."""
    return {k: LayerOf(v, layer) for k, v in stacks.items()}


def expert_product(rows: int, backends: Backends) -> str:
    """The product an expert layer of ``rows`` rows takes, a static fact of
    its program (the label ``expert_product``). Where the runner's record
    says the experts are whole on one device, a kernel of engine/experts.py
    that reads each chosen expert once as stored: "touched" up to
    MOE_DENSE_MAX_ROWS rows (a window's step, a short chunk: every row by
    the experts the live rows chose), "grouped" above (sorted pairs, each
    by its own expert); on any mesh "masked" at every size. A program whose
    layers take a kernel hands them the expert stacks whole
    (``scan_layers``)."""
    if not backends.experts_whole:
        return "masked"
    return "touched" if rows <= MOE_DENSE_MAX_ROWS else "grouped"


def moe_route(router: jax.Array, spec: ModelSpec,
              bias: jax.Array | None = None) -> tuple[jax.Array, jax.Array]:
    """Router logits [T, E] float32 -> (gates [T, k] float32, experts
    [T, k]). "topk_softmax" (Mixtral): the k largest logits, softmax over
    those. "softmax_topk" (SmallThinker): softmax over all E, the k largest
    probabilities, divided by their sum when norm_topk_prob.
    "sigmoid_topk" (Cohere2-MoE): the same with a sigmoid of each logit in
    place of the softmax. E is the router's width: the sum runs over all k
    chosen, whichever device holds them.

    DeepSeek-V3's grouped choice on top of "sigmoid_topk": ``bias`` [E]
    (float32; ``moe_select_bias``) is added to the scores for the CHOICE
    alone; with ``n_group`` > 1 the E experts are n_group runs of equal
    length, a group's score is the sum of its 2 largest biased scores, and
    the k are taken among the experts of the ``topk_group`` best groups;
    the gates are the chosen experts' unbiased scores, normalised as
    above, times ``routed_scaling_factor``."""
    if spec.moe_router in ("softmax_topk", "sigmoid_topk"):
        score = (jax.nn.sigmoid(router) if spec.moe_router == "sigmoid_topk"
                 else jax.nn.softmax(router, axis=-1))
        if bias is None and spec.n_group == 1:
            top_v, top_i = jax.lax.top_k(score, spec.num_experts_per_tok)
        else:
            choice = score if bias is None else score + bias
            if spec.n_group > 1:
                groups = choice.reshape(*choice.shape[:-1], spec.n_group, -1)
                best = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)
                _, keep = jax.lax.top_k(best, spec.topk_group)
                kept = jnp.any(jax.nn.one_hot(keep, spec.n_group,
                                              dtype=jnp.bool_), axis=-2)
                choice = jnp.where(kept[..., None], groups,
                                   -jnp.inf).reshape(choice.shape)
            _, top_i = jax.lax.top_k(choice, spec.num_experts_per_tok)
            top_v = jnp.take_along_axis(score, top_i, axis=-1)
        if spec.norm_topk_prob:
            top_v = top_v / jnp.sum(top_v, axis=-1, keepdims=True)
        if spec.routed_scaling_factor != 1.0:
            top_v = top_v * spec.routed_scaling_factor
        return top_v, top_i
    top_v, top_i = jax.lax.top_k(router, spec.num_experts_per_tok)
    return jax.nn.softmax(top_v, axis=-1), top_i           # over top-k


def _gate_act(gate: jax.Array, spec: ModelSpec,
              multiplier: float = 1.0) -> jax.Array:
    """SwiGLU's SiLU or ReGLU's ReLU on the gate projection (times
    ``multiplier``, muP's constant on the pre-activation), in float32;
    "relu2": the squared ReLU of a two-matrix expert's one projection.
    (``jnp.maximum``, not ``jax.nn.relu``: behind the latter XLA's CPU
    backend folds the converts away and is left with a bf16 x bf16 -> f32
    batched dot it cannot execute; the tests run there.)"""
    g = gate.astype(jnp.float32)
    if multiplier != 1.0:
        g = g * multiplier
    if spec.ffn_act == "relu2":
        return jnp.square(jnp.maximum(g, 0.0)).astype(jnp.bfloat16)
    act = jnp.maximum(g, 0.0) if spec.ffn_act == "relu" else jax.nn.silu(g)
    return act.astype(jnp.bfloat16)


def held_load(one_hot: jax.Array, live: jax.Array):
    """What the ``live`` rows [T] (bool) chose of the experts HELD: (load
    [E] float32, their picks an expert; walk [E] int32, the experts with a
    pick in rising order; count, how many those are: experts.touched).
    one_hot [T, k, E] over the held experts: a choice that fell on an
    expert held elsewhere is a row of zeros and counts nowhere. ONE place
    finds the set: the counter ``moe_touched`` and the visits of
    experts.touched_product are the same number by construction."""
    from dynamo_tpu.engine.experts import touched
    load = jnp.einsum("tke,t->e", one_hot, live.astype(jnp.float32))
    return (load, *touched(load))


def moe_load_stats(load: jax.Array, count: jax.Array, live: jax.Array,
                   spec: ModelSpec) -> jax.Array:
    """What one expert layer's routing did to the rows that are ``live``
    [T] (bool), counted over the experts HELD (``held_load``'s load and
    count): float32 [3] = (distinct held experts chosen, the fullest held
    expert's tokens over the mean an expert of the router's width would
    get, 1 if any row was live else 0). A layer that is told its share
    (num_routed_experts) adds two: the (row, choice) pairs that fell on
    held experts, and all pairs."""
    rows = jnp.sum(live.astype(jnp.float32))
    mean = rows * spec.num_experts_per_tok / spec.router_width
    some = rows > 0
    stats = [count.astype(jnp.float32),
             jnp.where(some, jnp.max(load) / jnp.maximum(mean, 1e-9), 0.0),
             some.astype(jnp.float32)]
    if spec.num_routed_experts is not None:
        stats += [jnp.sum(load), rows * spec.num_experts_per_tok]
    return jnp.stack(stats)


def _expert_stacks(lp: dict, *keys):
    """(stacks over all layers, their scales or None, the layer) of a
    layer's expert leaves ``keys`` as a kernel of engine/experts.py takes
    them: as scan_layers hands them whole, else this layer's as a stack of
    one."""
    ws, layer = [lp[key] for key in keys], 0
    if isinstance(ws[0], LayerOf):
        ws, layer = [w.stack for w in ws], ws[0].layer
    else:
        ws = [jax.tree.map(lambda a: a[None], w) for w in ws]
    if isinstance(ws[0], QTensor):
        return tuple(w.q for w in ws), tuple(w.s for w in ws), layer
    return tuple(ws), None, layer


def _grouped_experts(x: jax.Array, gates: jax.Array, top_i: jax.Array,
                     lp: dict, spec: ModelSpec, interpret: bool = False
                     ) -> jax.Array:
    """The chosen experts' outputs summed under their gates, computing only
    what was chosen of the experts HELD: each (token, choice) pair is a row,
    rows sorted by held expert, and experts.pairs_product multiplies each
    group by its own expert as stored (gate and up in one call, or a
    two-matrix expert's up with its activation; down in a second). top_i
    counts from the first expert held: a choice outside [0, num_experts)
    fell on an expert held elsewhere, sorts behind the last group and adds
    nothing. x [T, H] bf16; returns [T, H] float32."""
    from dynamo_tpu.engine.experts import ROW_TILE, pairs_product, visits
    t, k = top_i.shape
    held = (top_i >= 0) & (top_i < spec.num_experts)
    flat_e = jnp.where(held, top_i, spec.num_experts).reshape(-1)  # [T*k]
    order = jnp.argsort(flat_e)                              # stable
    sizes = jnp.sum(flat_e[:, None] == jnp.arange(spec.num_experts)[None, :],
                    axis=0, dtype=jnp.int32)
    rows = jnp.pad(x[order // k], ((0, -(t * k) % ROW_TILE), (0, 0)))
    walk = visits(sizes, rows.shape[0])

    ff = pairs_product(
        rows, *_expert_stacks(lp, *(k for k in EXPERT_LEAVES[:2] if k in lp)),
        walk, act=spec.ffn_act, interpret=interpret)
    down = pairs_product(ff, *_expert_stacks(lp, "moe_w_down"), walk,
                         interpret=interpret)                # [T*k+, H] f32
    # Back to (token, choice) order by a gather, then the gated sum over k;
    # a pair of no group reads whatever the kernel's buffers held: zeros.
    down = down[jnp.argsort(order)].reshape(t, k, -1)
    return jnp.einsum("tkh,tk->th", jnp.where(held[..., None], down, 0.0),
                      gates)


def ffn_block(h2: jax.Array, lp: dict, spec: ModelSpec, ll: dict | None = None,
              ids: jax.Array | None = None, router_in: jax.Array | None = None,
              live: jax.Array | None = None, backends: Backends = XLA):
    """Feed-forward over normalized hidden states [..., H]: dense SwiGLU /
    ReGLU, or a routed expert layer when spec.num_experts > 0.

    Routed formulation (TPU-first): ``moe_route`` gates the chosen experts;
    the router reads ``router_in`` (SmallThinker: the layer's input) or h2.
    ``expert_product`` says how the experts are multiplied. Where the
    expert axis may be partitioned (any mesh), at every size: every
    RESIDENT expert computes the whole token batch and the combine
    contracts over the expert axis under the gate mask: with experts
    sharded over "tp" each device runs E/tp experts and XLA inserts the
    psum, i.e. expert parallelism without a dynamic all-to-all. Where the
    runner's record says the experts are whole on one device
    (``backends.experts_whole``: its mesh has one device;
    ``backends.interpret``: the CPU, which interprets the kernels), every
    routed kind, gated experts and two-matrix ones ("relu2": no gate leaf)
    alike, reads the chosen experts alone through a Pallas kernel of
    engine/experts.py (which GSPMD cannot partition): up to
    MOE_DENSE_MAX_ROWS rows every row is multiplied by the held experts
    that a row which counts chose (``experts.touched_product``; the rows
    that count are the ``live`` ones, every row without ``live``: a slot
    that is not live makes no visit and its output is zero), above it the
    (row, choice) pairs are multiplied by their own experts only
    (``_grouped_experts``); a layer that holds a SHARE of a wider router's
    experts does the same with the picks that fell on the experts it holds.

    The router is as wide as the deployment has experts and its gates are
    normalised over all the chosen; this device multiplies the experts it
    HOLDS (``first_expert`` on, ``num_experts`` of them) and what the others
    would add is left out: no exchange, nothing in its place. Shared
    experts (``num_shared_experts``) take every row and their mean is added.

    With ``live`` ([T] bool, routed blocks only) returns (out, stats) with
    ``moe_load_stats`` of the live rows; else out. A leading dense layer of
    a routed model (``lp`` holds no router) takes the dense branch."""
    if not spec.num_experts or "moe_gate" not in lp:
        # Dense-MLP LoRA targets (gathered per-row deltas; MoE expert
        # weights are not adapter targets — attention-only there, so the
        # stacks simply lack the MLP keys).
        mlp_lora = ll is not None and "w_gate" in ll
        gate = mm(h2, lp["w_gate"], "...h,hi->...i")
        up = mm(h2, lp["w_up"], "...h,hi->...i")
        if mlp_lora:
            gate = gate + lora_delta(h2, ll["w_gate"], ids)
            up = up + lora_delta(h2, ll["w_up"], ids)
        # muP (``spec.mlp_multipliers``): a constant on the gate's
        # pre-activation and one on the down product.
        on_gate, on_down = spec.mlp_multipliers or (1.0, 1.0)
        ff = _gate_act(gate, spec, on_gate) * up
        down = mm(ff, lp["w_down"], "...i,ih->...h")
        if mlp_lora:
            down = down + lora_delta(ff, ll["w_down"], ids)
        return times(down, on_down)
    orig = h2.shape
    x = h2.reshape(-1, orig[-1])                       # [T, H]
    with scope("moe.router"):
        rin = x if router_in is None else router_in.reshape(-1, orig[-1])
        router = jnp.einsum("th,he->te", rin, lp["moe_gate"],
                            preferred_element_type=jnp.float32)
        bias = (lp["moe_bias"][:, 0].astype(jnp.float32)
                if spec.moe_select_bias else None)
        gates, top_i = moe_route(router, spec, bias)
        if spec.holds_share:
            # Counted from the first expert held: a choice that fell on
            # an expert held elsewhere has no column in one_hot.
            top_i = top_i - spec.first_expert
        one_hot = jax.nn.one_hot(top_i, spec.num_experts, dtype=jnp.float32)
        # The rows that count: a window's live slots, else every row.
        on = (jnp.ones(x.shape[:1], jnp.bool_) if live is None
              else live.reshape(-1))
        load, walk, count = held_load(one_hot, on)
        stats = (None if live is None
                 else moe_load_stats(load, count, on, spec))
    with scope("moe.experts"):
        product = expert_product(x.shape[0], backends)
        if product == "grouped":
            out = _grouped_experts(x, gates, top_i, lp, spec,
                                   interpret=backends.interpret)
        else:
            w_te = jnp.einsum("tk,tke->te", gates, one_hot)  # [T, E] sparse-ish
            if product == "touched":
                # A slot that is not live chooses nothing: no visit on its
                # account, and its output is zero.
                from dynamo_tpu.engine.experts import touched_product
                out = touched_product(
                    x, jnp.where(on[:, None], w_te, 0.0), *_expert_stacks(
                        lp, *(k for k in EXPERT_LEAVES if k in lp)),
                    walk, count, act=spec.ffn_act,
                    interpret=backends.interpret)
            else:
                down = _every_expert(x, lp.get("moe_w_gate"), lp["moe_w_up"],
                                     lp["moe_w_down"], spec)
                out = jnp.einsum("eth,te->th", down, w_te)
    if spec.num_shared_experts:
        with scope("moe.shared"):
            # Every row through every shared expert; their mean joins the
            # routed sum ("shared_expert_combination_strategy": "average").
            shared = _every_expert(x, lp.get("shared_w_gate"),
                                   lp["shared_w_up"], lp["shared_w_down"],
                                   spec)
            out = out + jnp.mean(shared, axis=0)
    out = out.astype(jnp.bfloat16).reshape(orig)
    return out if live is None else (out, stats)


def _every_expert(x: jax.Array, w_gate, w_up, w_down, spec: ModelSpec
                  ) -> jax.Array:
    """Every expert of a stack [E, ...] on every row: x [T, H] bf16 ->
    [E, T, H] float32, SwiGLU / ReGLU by ``spec.ffn_act``; without a gate
    matrix (``w_gate`` None, "relu2") down(relu(up x) ** 2)."""
    up = mm(x, w_up, "th,ehi->eti")
    if w_gate is None:
        ff = _gate_act(up, spec)
    else:
        ff = _gate_act(mm(x, w_gate, "th,ehi->eti"), spec) * up
    if isinstance(w_down, QTensor):
        return (jnp.einsum("eti,eih->eth", ff, w_down.q.astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32) * w_down.s)
    return jnp.einsum("eti,eih->eth", ff, w_down,
                      preferred_element_type=jnp.float32)


def init_params(spec: ModelSpec, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random init (bench/smoke). Real weights come from the safetensors
    loader (dynamo_tpu.engine.weights)."""
    shapes = param_shapes(spec)
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(leaves))

    def init_one(shape, k):
        if len(shape) == 1 or shape[-1] == 1:
            return jnp.ones(shape, dtype)  # norm scales
        fan_in = shape[-2] if len(shape) > 1 else shape[-1]
        return (jax.random.normal(k, shape, dtype)
                * (1.0 / jnp.sqrt(fan_in)).astype(dtype))

    inited = [init_one(s, k) for s, k in zip(leaves, keys)]
    params = jax.tree.unflatten(treedef, inited)
    # Norm scales must be ones.
    params["final_norm"] = jnp.ones(shapes["final_norm"], dtype)
    for name, shape in shapes["layers"].items():
        if name.endswith("_norm"):
            params["layers"][name] = jnp.ones(shape, dtype)
        elif name.endswith(("_bias", "_gain")):
            # [..., n, 1]: small beside a score, or beside the stream
            params["layers"][name] = (
                jax.random.normal(jax.random.fold_in(key, len(name)), shape,
                                  dtype) * (shape[-2] ** -0.5))
    return params


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def layer_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """Mean-centred LayerNorm without bias, in float32 inside."""
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def norm(x: jax.Array, scale: jax.Array, spec: ModelSpec) -> jax.Array:
    """The model's norm: RMSNorm or, by ``spec.norm_kind``, LayerNorm."""
    if spec.norm_kind == "layer":
        return layer_norm(x, scale, spec.rms_norm_eps)
    return rms_norm(x, scale, spec.rms_norm_eps)


def yarn_frequencies(dim: int, theta: float, yarn: tuple) -> jax.Array:
    """YaRN's dim // 2 frequencies: f_i = theta ** (-2i / dim) where a
    rotation is fast (more than beta_fast turns over the original
    context), f_i / factor where it is slow (fewer than beta_slow), a
    linear ramp over the frequency index between: d(r) = dim ln(original /
    (2 pi r)) / (2 ln theta), lo = floor(d(beta_fast)), hi =
    ceil(d(beta_slow)), ramp_i = clip((i - lo) / (hi - lo), 0, 1), f'_i =
    f_i (1 - ramp_i) + f_i / factor * ramp_i. cos and sin are not scaled
    (mscale equals mscale_all_dim): the attention's scale carries it
    (DeepseekV32Spec.attn_scale)."""
    factor, original, beta_fast, beta_slow = yarn[:4]
    half = dim // 2

    def turns_dim(r):
        return dim * math.log(original / (2 * math.pi * r)) \
            / (2 * math.log(theta))

    lo = max(math.floor(turns_dim(beta_fast)), 0)
    hi = min(math.ceil(turns_dim(beta_slow)), dim - 1)
    i = jnp.arange(half, dtype=jnp.float32)
    ramp = jnp.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    freqs = 1.0 / (theta ** (i / half))
    return freqs * (1.0 - ramp) + freqs / factor * ramp


def spec_rope_tables(spec: ModelSpec, positions: jax.Array):
    """``rope_tables`` of what ``spec`` rotates: a whole head at plain
    frequencies, or the latent block's rope part at YaRN's."""
    if spec.latent:
        return rope_tables(positions, spec.qk_rope_head_dim, spec.rope_theta,
                           spec.rope_yarn)
    return rope_tables(positions, spec.head_dim, spec.rope_theta)


def rope_tables(positions: jax.Array, head_dim: int, theta: float,
                yarn: tuple | None = None) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for HF rotate-half RoPE; positions [...]."""
    half = head_dim // 2
    if yarn is not None:
        freqs = yarn_frequencies(head_dim, theta, yarn)
    else:
        freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [..., half]
    cos = jnp.cos(angles)
    sin = jnp.sin(angles)
    return cos, sin


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               interleaved: bool = False) -> jax.Array:
    """x [..., heads, head_dim]; cos/sin [..., half] (broadcast over heads).
    Frequency i turns the pair (i, i + half) (rotate-half) or, with
    ``interleaved`` (GPT-J), the pair (2i, 2i + 1)."""
    half = x.shape[-1] // 2
    if interleaved:
        pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], half, 2)
        xf1, xf2 = pairs[..., 0], pairs[..., 1]
        cos, sin = cos[..., None, :], sin[..., None, :]
        out = jnp.stack([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], -1)
        return out.reshape(x.shape).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    cos = cos[..., None, :]
    sin = sin[..., None, :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out1 = xf1 * cos - xf2 * sin
    out2 = xf2 * cos + xf1 * sin
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


def ring_causal_attention(mesh, q: jax.Array, k: jax.Array, v: jax.Array,
                          q_positions: jax.Array, kv_len_mask: jax.Array,
                          q_per_kv: int) -> jax.Array:
    """Ring attention over the "sp" mesh axis (blockwise causal prefill
    attention with online softmax; Liu et al.'s ring attention shape,
    lax-level).

    The GSPMD sp path all-gathers the full K/V onto every shard before
    the quadratic scores — O(s) memory per device in sequence length.
    Here each sp shard keeps its sequence block resident and the K/V
    blocks ROTATE around the ring (lax.ppermute neighbor exchange over
    ICI), with a running (max, sum, acc) online softmax — peak K/V
    memory is one block, and each hop's transfer overlaps the previous
    block's matmul in XLA's schedule. Queries never move (they are the
    larger tensor with GQA).

    q [B,S,Nh,D], k/v [B,S,Nkv,D], q_positions [B,S] absolute,
    kv_len_mask [B,S] — sequence-sharded over "sp" AND head-sharded over
    "tp" (both axes stay manual in the shard_map, so tp keeps its
    head-parallel split instead of being all-gathered; the head-major
    [nkv, g] layout keeps each kv group's q heads on the group's tp
    shard, so GQA grouping is shard-local). Causality rides the ABSOLUTE
    positions travelling with each block, so no step/offset bookkeeping
    is needed. The ring loop is UNROLLED over the (static, small) shard
    count: the last block skips the rotation — a fori_loop would pay one
    dead full-K/V neighbor hop per layer. fp32 accumulation, bf16 matmul
    operands — same numerics recipe as the dense path. The reference has
    no sequence parallelism at all (SURVEY §2.7); this is a
    beyond-parity capability."""
    from jax import shard_map

    n_shards = mesh.shape["sp"]
    scale = 1.0 / math.sqrt(q.shape[-1])

    def local(q_blk, k_blk, v_blk, qpos_blk, kmask_blk):
        b, sq, nh, d = q_blk.shape  # nh, nkv are per-tp-shard counts here
        nkv = k_blk.shape[2]
        qg = q_blk.reshape(b, sq, nkv, q_per_kv, d)
        m = jnp.full((b, nkv, q_per_kv, sq), -1e30, jnp.float32)
        l = jnp.zeros((b, nkv, q_per_kv, sq), jnp.float32)
        acc = jnp.zeros((b, nkv, q_per_kv, sq, d), jnp.float32)
        k_c, v_c = k_blk, v_blk
        kpos, kmask = qpos_blk, kmask_blk
        perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
        for t in range(n_shards):
            if t > 0:  # rotate-before-compute: no dead final hop
                k_c = jax.lax.ppermute(k_c, "sp", perm)
                v_c = jax.lax.ppermute(v_c, "sp", perm)
                kpos = jax.lax.ppermute(kpos, "sp", perm)
                kmask = jax.lax.ppermute(kmask, "sp", perm)
            s = jnp.einsum("bqngd,bknd->bngqk", qg, k_c,
                           preferred_element_type=jnp.float32) * scale
            ok = ((qpos_blk[:, None, None, :, None]
                   >= kpos[:, None, None, None, :])
                  & kmask[:, None, None, None, :])
            s = jnp.where(ok, s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            # where() rather than bare exp: an all-masked block would
            # otherwise yield exp(-1e30 - (-1e30)) = 1 per masked key.
            p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            acc = (acc * corr[..., None]
                   + jnp.einsum("bngqk,bknd->bngqd",
                                p.astype(jnp.bfloat16), v_c
                                ).astype(jnp.float32))
            m = m_new
        out = acc / jnp.maximum(l, 1e-9)[..., None]
        # [B,Nkv,G,sq,D] -> [B,sq,Nh,D]
        return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, nh, d) \
            .astype(q_blk.dtype)

    seq_heads = P(None, "sp", "tp", None)
    return shard_map(
        local, mesh=mesh,
        in_specs=(seq_heads, seq_heads, seq_heads,
                  P(None, "sp"), P(None, "sp")),
        out_specs=seq_heads)(q, k, v, q_positions, kv_len_mask)


def dense_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           q_positions: jax.Array, kv_len_mask: jax.Array,
                           q_per_kv: int, reach: jax.Array | None = None
                           ) -> jax.Array:
    """Prefill attention over freshly-computed K/V.

    q [B,S,Nh,D], k/v [B,S,Nkv,D], q_positions [B,S] (absolute), kv_len_mask
    [B,S] bool (valid kv slots). Causal by position. fp32 accumulation.
    GQA handled by grouping q heads (no materialized repeat). ``reach``
    (``window_reach``; None: every earlier key) is how far back a query
    sees: key j iff i - reach < j <= i.
    """
    b, s, nh, d = q.shape
    nkv = k.shape[2]
    qg = q.reshape(b, s, nkv, q_per_kv, d)
    scores = jnp.einsum("bqngd,bknd->bngqk", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(d))
    causal = (q_positions[:, None, None, :, None]
              >= q_positions[:, None, None, None, :])
    valid = kv_len_mask[:, None, None, None, :]
    if reach is not None:
        valid = valid & (q_positions[:, None, None, :, None] - reach
                         < q_positions[:, None, None, None, :])
    scores = jnp.where(causal & valid, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bngqk,bknd->bqngd", probs, v)
    return out.reshape(b, s, nh, d)


#: Float32 attention scores of one with-history prefill call (rows x heads x
#: chunk x (history + chunk)) up to which every KV head's are computed at
#: once; above it a KV head at a time. At 128 query heads a chunk of 1,024
#: tokens over 4,096 of history is 2.7 GB of scores, more than a v5e has
#: left beside 9.3 GB of weights and the pool (compiled for a described
#: v5e, PR 32); 28 heads at the same shape are 0.6 GB.
HISTORY_SCORE_BYTES = 1 << 30


def history_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      k_hist: jax.Array, v_hist: jax.Array,
                      positions: jax.Array, valid: jax.Array,
                      hist_lens: jax.Array, spec: ModelSpec,
                      reach=None, limit: int = HISTORY_SCORE_BYTES
                      ) -> jax.Array:
    """A prefill chunk's attention over itself and the row's earlier pages:
    q [B,S,Nh,D], k/v [B,S,Nkv,D] the chunk's own, k_hist/v_hist
    [Nkv,B,L,D] the gathered pages (history token l stands at position l,
    ``hist_lens`` [B] of them are real), ``reach`` ``window_reach`` of the
    layer, ``limit`` the score bytes up to which the KV heads go at once.
    Returns [B,S,Nh*D]."""
    b, s = positions.shape
    d, nkv = spec.head_dim, spec.num_kv_heads
    hist_len = k_hist.shape[2]

    def heads(qg, k, v, k_hist, v_hist):
        """qg [b,s,n,g,d], k/v [b,s,n,d], k_hist/v_hist [n,b,l,d]
        for n of the KV heads -> [b,s,n,g,d]."""
        # In-chunk causal scores (grouped GQA, no repeat).
        chunk_scores = jnp.einsum("bqngd,bknd->bngqk", qg, k,
                                  preferred_element_type=jnp.float32)
        causal = (positions[:, None, None, :, None]
                  >= positions[:, None, None, None, :])
        seen = causal & valid[:, None, None, None, :]
        if reach is not None:
            seen = seen & (positions[:, None, None, :, None] - reach
                           < positions[:, None, None, None, :])
        chunk_scores = jnp.where(seen, chunk_scores, -1e30)
        hist_scores = jnp.einsum("bqngd,nbld->bngql", qg, k_hist,
                                 preferred_element_type=jnp.float32)
        hist_pos = jnp.arange(hist_len)[None, :]
        hist_valid = (hist_pos
                      < hist_lens[:, None])[:, None, None, None, :]
        if reach is not None:
            # History token l stands at position l.
            hist_valid = hist_valid & (
                positions[:, None, None, :, None] - reach
                < hist_pos[:, None, None, None, :])
        hist_scores = jnp.where(hist_valid, hist_scores, -1e30)
        scores = jnp.concatenate([hist_scores, chunk_scores], axis=-1)
        scores = scores / jnp.sqrt(jnp.float32(d))
        probs = jax.nn.softmax(scores, axis=-1).astype(jnp.bfloat16)
        p_hist, p_chunk = jnp.split(probs, [hist_len], axis=-1)
        return (jnp.einsum("bngql,nbld->bqngd", p_hist, v_hist)
                + jnp.einsum("bngqk,bknd->bqngd", p_chunk, v))

    qg = q.reshape(b, s, nkv, spec.q_per_kv, d)
    score_bytes = 4 * b * spec.num_heads * s * (hist_len + s)
    if score_bytes <= limit or nkv == 1:
        attn = heads(qg, k, v, k_hist, v_hist)
    else:
        # A KV head at a time: every head's scores at once would not fit
        # beside the weights and the pool.
        one = jax.lax.map(
            lambda a: heads(*(x[:, :, None] for x in a[:3]),
                            *(x[None] for x in a[3:])),
            (jnp.moveaxis(qg, 2, 0), jnp.moveaxis(k, 2, 0),
             jnp.moveaxis(v, 2, 0), k_hist, v_hist))
        attn = jnp.moveaxis(one[:, :, :, 0], 0, 2)
    return attn.reshape(b, s, -1)


def paged_decode_attention_xla(q: jax.Array, k_cache: jax.Array,
                               v_cache: jax.Array, layer: jax.Array,
                               page_table: jax.Array, hist_lens: jax.Array,
                               k_self: jax.Array, v_self: jax.Array,
                               q_per_kv: int, lo: jax.Array | None = None
                               ) -> jax.Array:
    """Gather-based decode attention over the FULL stacked cache.

    q [B,Nh,D]; k_cache/v_cache [L,Nkv,P,page,D]; layer: scalar layer index;
    page_table [B,maxP]; hist_lens [B] = tokens already IN the cache (the
    new token travels as k_self/v_self [B,Nkv,D] — its cache write is
    deferred so the whole forward needs only ONE scatter; see
    decode_forward). The layer index is folded into the gather itself —
    never slice the cache (a dynamic-slice copy of cache/L per layer is the
    difference between 1.5 ms and 50 ms steps at multi-GB pools).

    This is the window attention with zero in-window columns."""
    b = q.shape[0]
    nkv, d = k_cache.shape[1], k_cache.shape[4]
    empty = jnp.zeros((nkv, b, 0, d), k_cache.dtype)
    return paged_window_attention_xla(
        q, k_cache, v_cache, layer, page_table, hist_lens, empty, empty,
        jnp.asarray(0, jnp.int32), k_self, v_self, q_per_kv, lo=lo)


def paged_window_attention_xla(q: jax.Array, k_cache: jax.Array,
                               v_cache: jax.Array, layer: jax.Array,
                               page_table: jax.Array, hist_lens: jax.Array,
                               k_win: jax.Array, v_win: jax.Array,
                               m: jax.Array, k_self: jax.Array,
                               v_self: jax.Array, q_per_kv: int,
                               lo: jax.Array | None = None) -> jax.Array:
    """Decode attention for step ``m`` of an M-step window.

    Keys/values come from three places: pages already in the cache
    (hist_lens tokens, read via a layer-folded gather), the in-window
    buffer k_win/v_win [Nkv,B,M,D] holding this window's previous steps
    (cols j < m valid), and the current token (k_self/v_self [B,Nkv,D]).
    The cache itself is read-only here — the window's writes are committed
    by ONE scatter after the step scan, which is what lets XLA run the
    whole window without copying the multi-GB pool (see runner._get_window).
    ``lo`` [B] (None: 0) is the first position a row's query still sees (a
    sliding-window layer's ``window_lo``); in-window column j stands at
    position hist_lens + j.
    """
    b, nh, d = q.shape
    nkv, page = k_cache.shape[1], k_cache.shape[3]
    maxp = page_table.shape[1]
    M = k_win.shape[2]
    # Layer+head-folded gather straight into the dot's [Nkv,B,L,D]
    # operand layout (no transposed relayout of the gathered history);
    # dequantizes int8 pools inside the gather expression.
    with scope("attn.kv_gather"):
        k_all = gather_pages_folded(k_cache, layer, page_table)
        v_all = gather_pages_folded(v_cache, layer, page_table)
    qg = q.reshape(b, nkv, q_per_kv, d)
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    s_hist = jnp.einsum("bngd,nbld->bngl", qg, k_all,
                        preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(maxp * page)[None, :]
    hist_valid = pos < hist_lens[:, None]
    if lo is not None:
        hist_valid = hist_valid & (pos >= lo[:, None])
    s_hist = jnp.where(hist_valid[:, None, None, :], s_hist, -1e30)
    s_win = jnp.einsum("bngd,nbjd->bngj", qg, k_win,
                       preferred_element_type=jnp.float32) * scale
    win_valid = jnp.arange(M)[None, :] < m
    if lo is not None:
        win_valid = win_valid & (hist_lens[:, None] + jnp.arange(M)[None, :]
                                 >= lo[:, None])
    win_valid = win_valid[:, None, None, :]
    s_win = jnp.where(jnp.broadcast_to(win_valid, s_win.shape), s_win, -1e30)
    s_self = jnp.einsum("bngd,bnd->bng", qg, k_self,
                        preferred_element_type=jnp.float32)[..., None] * scale
    full = jnp.concatenate([s_hist, s_win, s_self], axis=-1)
    probs = jax.nn.softmax(full, axis=-1)
    p_hist = probs[..., :maxp * page].astype(q.dtype)
    p_win = probs[..., maxp * page:-1].astype(q.dtype)
    p_self = probs[..., -1]
    out = (jnp.einsum("bngl,nbld->bngd", p_hist, v_all)
           + jnp.einsum("bngj,nbjd->bngd", p_win, v_win)
           + p_self[..., None].astype(q.dtype) * v_self[:, :, None, :])
    return out.reshape(b, nh, d)


def kv_attention(backends: Backends, window: bool):
    """Who attends a pool of K and V pages, in a window's step
    (``paged_window_attention_xla``'s signature) or in the single decode
    step (``paged_decode_attention_xla``'s): the kernel the runner's record
    binds, else XLA's gather. The one place a forward program asks."""
    return backends.kv_reader(window) or (
        paged_window_attention_xla if window else paged_decode_attention_xla)


def _split_heads(x, n, d):
    return x.reshape(*x.shape[:-1], n, d)

# ---------------------------------------------------------------------------
# Latent attention with a learned selection of keys (DeepSeek-V3.2)
# ---------------------------------------------------------------------------

class LatentQuery(NamedTuple):
    """What ``attend`` gets as ``q`` in the latent block: the heads' nope
    and rope parts, the indexer's query heads and head weights, and the two
    halves of Wkv_b, which prefill multiplies the latent by (expanded form)
    and decode folds into the query and the output (absorbed form)."""
    nope: jax.Array         # [..., Nh, qk_nope_head_dim]
    rope: jax.Array         # [..., Nh, qk_rope_head_dim], rotated
    iq: Any                 # [..., index_n_heads, index_head_dim], or None
    iw: Any                 # [..., index_n_heads] float32 (no indexer: None)
    wk_b: Any               # [kv_lora_rank, Nh * qk_nope_head_dim]
    wv_b: Any               # [kv_lora_rank, Nh * v_head_dim]


def latent_qkv(h: jax.Array, lp: dict, spec: ModelSpec, cos: jax.Array,
               sin: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array,
                                         jax.Array]:
    """The latent projections of normalized states h [..., H]: (cq
    [..., q_lora_rank], q nope [..., Nh, nope], q rope [..., Nh, rope]
    rotated, entry [..., 1, width]). The entry is what the token leaves in
    the first pool: the normalized latent, the ONE rope key every head
    shares (rotated), zeros up to ``spec.kv_entry``'s width."""
    eps = spec.rms_norm_eps
    cq = rms_norm(mm(h, lp["wq_a"], "...h,hr->...r"), lp["q_a_norm"], eps)
    q = _split_heads(mm(cq, lp["wq_b"], "...r,rd->...d"), spec.num_heads,
                     spec.head_dim)
    nope = q[..., :spec.qk_nope_head_dim]
    rope = apply_rope(q[..., spec.qk_nope_head_dim:], cos, sin,
                      spec.rope_interleaved)
    ckv = mm(h, lp["wkv_a"], "...h,hr->...r")
    c = rms_norm(ckv[..., :spec.kv_lora_rank], lp["kv_a_norm"], eps)
    kr = apply_rope(ckv[..., None, spec.kv_lora_rank:], cos, sin,
                    spec.rope_interleaved)[..., 0, :]
    width = spec.kv_entry[1][0]
    pad = jnp.zeros((*c.shape[:-1], width - c.shape[-1] - kr.shape[-1]),
                    c.dtype)
    return cq, nope, rope, jnp.concatenate([c, kr, pad], -1)[..., None, :]


def index_qk(h: jax.Array, cq: jax.Array, lp: dict, spec: ModelSpec,
             cos: jax.Array, sin: jax.Array):
    """The indexer's projections: (query heads [..., J, Di], head weights
    [..., J] float32, key [..., 1, Di]). The first qk_rope_head_dim dims of
    each query head and of the key turn in rotate-half pairs at the
    attention's own frequencies; the key is a LayerNorm WITH bias (eps
    1e-6) of h WIk; the weights carry J ** -0.5 * Di ** -0.5."""
    J, di, r = spec.index_n_heads, spec.index_head_dim, spec.qk_rope_head_dim

    def turned(x):       # [..., heads, Di]
        return jnp.concatenate(
            [apply_rope(x[..., :r], cos, sin), x[..., r:]], axis=-1)

    iq = turned(_split_heads(mm(cq, lp["index_wq_b"], "...r,rd->...d"),
                             J, di))
    k = mm(h, lp["index_wk"], "...h,hd->...d")
    k = layer_norm(k, lp["index_k_norm"], 1e-6) + lp["index_k_bias"][:, 0]
    ik = turned(k[..., None, :])
    iw = jnp.einsum("...h,hj->...j", h, lp["index_w"],
                    preferred_element_type=jnp.float32) * (J * di) ** -0.5
    return iq, iw, ik


#: Float32 bytes up to which the indexer's per-head scores, and a prefill
#: call's attention scores, are one product. A quarter of the K-and-V
#: path's runner.HISTORY_SCORE_BYTES, and not that bound: at exactly 1 GiB
#: (8 rows x 128 heads x 512 x 512) a whole-prompt group's scores and as
#: much again of exponentials are 3.3 GB of temporaries beside 13.5 GB of
#: weights and pool, 1.4 GB in blocks of this size (compiled for a
#: described v5e, PR 34).
LATENT_SCORE_BYTES = 256 << 20


def index_scores(iq: jax.Array, iw: jax.Array, ik: jax.Array) -> jax.Array:
    """I[b, t, s] = sum_j iw[b, t, j] relu(iq[b, t, j] . ik[b, s]), float32.
    iq [B, T, J, Di], iw [B, T, J], ik [B, S, Di]. A head at a time where
    [B, T, J, S] float32 would pass LATENT_SCORE_BYTES."""
    b, t, j, _ = iq.shape
    s = ik.shape[1]

    def of(iq, iw):                       # [B, T, j', Di], [B, T, j']
        dots = jnp.einsum("btjd,bsd->btjs", iq, ik,
                          preferred_element_type=jnp.float32)
        return jnp.einsum("btjs,btj->bts", jnp.maximum(dots, 0.0), iw)

    if 4 * b * t * j * s <= LATENT_SCORE_BYTES:
        return of(iq, iw)
    total, _ = jax.lax.scan(
        lambda acc, x: (acc + of(x[0][:, :, None], x[1][:, :, None]), None),
        jnp.zeros((b, t, s), jnp.float32),
        (jnp.moveaxis(iq, 2, 0), jnp.moveaxis(iw, 2, 0)))
    return total


def select_topk(scores: jax.Array, valid: jax.Array, k: int) -> jax.Array:
    """Bool mask [..., S] of the k valid entries of largest ``scores``
    (float32) along the last axis, every valid one where there are fewer:
    the SET ``jax.lax.top_k`` returns, without its sort. The k-th largest
    value is found by bisection on the scores' bit patterns (an
    order-preserving int32 of a float32), 32 counts over the row; a sort at
    k = 2,048 of 8,192 costs more than the rest of the indexer (PERF.md
    section 6, PR 34). Entries that TIE with the k-th value are all kept
    (``top_k`` keeps the lower indices): more than k only where float32
    scores of distinct keys are equal across rank k (none at 64 index heads;
    ranking them took a cumulative sum over the row, as much as ten of the
    counts)."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    key = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
    bottom = jnp.int32(-2 ** 31)
    key = jnp.where(valid, jnp.maximum(key, bottom + 1), bottom)

    def halve(_, lohi):
        lo, hi = lohi       # count(key >= lo) >= k, or lo is the bottom
        mid = (lo >> 1) + (hi >> 1) + (((lo & 1) + (hi & 1) + 1) >> 1)
        enough = jnp.sum(key >= mid[..., None], axis=-1) >= k
        return jnp.where(enough, mid, lo), jnp.where(enough, hi, mid - 1)

    shape = scores.shape[:-1]
    kth, _ = jax.lax.fori_loop(
        0, 32, halve, (jnp.full(shape, bottom), jnp.full(shape, 2 ** 31 - 1,
                                                         jnp.int32)))
    return (key >= kth[..., None]) & valid


def _weight(w, heads: int):
    """A [rank, heads * d] leaf as (matrix [rank, heads, d] in bfloat16 as
    stored, float32 scale [heads, d] or None)."""
    if isinstance(w, QTensor):
        return (w.q.reshape(w.q.shape[0], heads, -1).astype(jnp.bfloat16),
                w.s.reshape(heads, -1))
    return w.reshape(w.shape[0], heads, -1), None


def latent_prefill_attention(q: LatentQuery, entry: jax.Array,
                             ik: jax.Array, positions: jax.Array,
                             valid: jax.Array, spec: ModelSpec,
                             hist: tuple | None = None) -> jax.Array:
    """Prefill attention of the latent block in the EXPANDED form: every
    head's key (c Wk_b | the shared rope key) and value c Wv_b are made
    from the entries, over the chunk's own tokens and, with ``hist``
    (entries [B, Lh, width], index keys [B, Lh, Di], hist_lens [B]: the
    row's earlier pages, token l at position l), its history. q's leaves
    [B, S, ...]; entry [B, S, 1, width], ik [B, S, 1, Di]; positions,
    valid [B, S]. Where a query can have more than ``index_topk`` keys
    (statically: history + chunk), it attends the index_topk of largest
    index score among those it may see. Returns [B, S, Nh * v_head_dim]."""
    b, s = positions.shape
    nh, r = spec.num_heads, spec.kv_lora_rank
    rope = spec.qk_rope_head_dim
    e, ki = entry[:, :, 0], ik[:, :, 0]
    seen = (positions[:, :, None] >= positions[:, None, :]) \
        & valid[:, None, :]                                  # [B, S, S]
    if hist is not None:
        e_hist, ki_hist, hist_lens = hist
        lh = e_hist.shape[1]
        e = jnp.concatenate([e_hist, e], axis=1)
        ki = jnp.concatenate([ki_hist, ki], axis=1)
        old = jnp.arange(lh)[None, None, :] < hist_lens[:, None, None]
        seen = jnp.concatenate(
            [jnp.broadcast_to(old, (b, s, lh)), seen], axis=-1)
    keys = e.shape[1]
    if spec.index_topk and keys > spec.index_topk:
        with scope("attn.index"):
            seen = select_topk(index_scores(q.iq, q.iw, ki), seen,
                               spec.index_topk)
    with scope("attn.core"):
        c, kr = e[..., :r], e[..., r:r + rope]
        kn = _split_heads(mm(c, q.wk_b, "bkr,rd->bkd"), nh,
                          spec.qk_nope_head_dim)
        v = _split_heads(mm(c, q.wv_b, "bkr,rd->bkd"), nh, spec.v_head_dim)

        def heads(x):
            """A block of heads: nope, rope [B,S,n,.], kn, v [B,K,n,.]."""
            qn, qr, kn, v = x
            scores = (jnp.einsum("bqnd,bknd->bnqk", qn, kn,
                                 preferred_element_type=jnp.float32)
                      + jnp.einsum("bqnd,bkd->bnqk", qr, kr,
                                   preferred_element_type=jnp.float32))
            scores = jnp.where(seen[:, None], scores * spec.attn_scale,
                               -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
            return jnp.einsum("bnqk,bknd->bqnd", probs, v)

        # Heads in blocks whose float32 scores stay under the limit: all
        # 128 heads of 8 rows of 512 tokens are 1.07 GB of scores and as
        # much again of exponentials, beside 8.4 GB of weights and the pool
        # (compiled for a described v5e, PR 34: 3.3 GB of temporaries
        # whole, 1.4 GB in blocks).
        block = nh
        while block > 1 and 4 * b * block * s * keys > LATENT_SCORE_BYTES:
            block //= 2
        if block == nh:
            out = heads((q.nope, q.rope, kn, v))
        else:
            split = lambda a: jnp.moveaxis(  # noqa: E731
                a.reshape(*a.shape[:2], nh // block, block, a.shape[-1]),
                2, 0)
            out = jax.lax.map(heads, tuple(
                split(a) for a in (q.nope, q.rope, kn, v)))
            out = jnp.moveaxis(out, 0, 2)
        return out.reshape(b, s, nh * spec.v_head_dim)


def latent_window_attention(q: LatentQuery, e_cache: jax.Array,
                            i_cache: jax.Array, layer: jax.Array,
                            page_table: jax.Array, hist_lens: jax.Array,
                            e_win: jax.Array, i_win: jax.Array, m: jax.Array,
                            e_self: jax.Array, i_self: jax.Array,
                            spec: ModelSpec, live: jax.Array | None = None,
                            backends: Backends = XLA):
    """Decode attention of the latent block for step ``m`` of a window, in
    the ABSORBED form: a head's query is folded through Wk_b into the
    latent's space (qa_h = q_nope_h Wk_b[h]^T), scores and the weighted
    sum run over the ENTRIES (the one key and value every head shares),
    and Wv_b expands the result. The same numbers as the expanded form.

    q's leaves [B, ...]; e_cache [L, 1, P, page, width] latent entries and
    i_cache [L, 1, P, page, Di] index keys under one page table; e_win /
    i_win [1, B, M, .] the window's earlier steps (columns < m), e_self /
    i_self [B, 1, .] this token. Keys come from the three places
    ``paged_window_attention_xla`` reads. Where the page-table bucket can
    hold more than ``index_topk`` keys the indexer scores every key in
    context and the row attends the index_topk of largest score.

    Who reads the pool (``backends.latent_readers``): on one TPU device
    the pair the runner's record binds (attention.latent_history_pallas,
    attention.latent_index_pallas). The indexer's kernel walks each row's
    live pages of index keys once and returns a float32 score a key; the
    reader's walks the row's live entries once with the choice as its mask,
    and hands back a running maximum, sum and weighted sum that are merged
    here with the window's columns and the self token. (None, None) (the
    CPU, any mesh) is XLA's walk, which gathers the whole bucket of every slot from
    both arrays, scores the index keys' copy and reads the entries' twice
    more. Either way XLA scores the keys that are not in the pool yet (the
    window's columns, the self token) and the choice over all of them stays
    ``select_topk``'s: PERF.md section 6, PR 37 has the measurements.
    Returns (attention [B, Nh * v_head_dim], float32 [2]: keys attended and
    keys in context, summed over the ``live`` rows)."""
    b = hist_lens.shape[0]
    nh, r = spec.num_heads, spec.kv_lora_rank
    page = e_cache.shape[3]
    maxp = page_table.shape[1]
    M = e_win.shape[2]
    hist = maxp * page
    pos = jnp.arange(hist)[None, :]
    seen = jnp.concatenate(
        [pos < hist_lens[:, None],
         jnp.broadcast_to(jnp.arange(M)[None, :] < m, (b, M)),
         jnp.ones((b, 1), bool)], axis=1)                    # [B, K]
    chosen = seen
    reader, indexer = backends.latent_readers()
    if spec.index_topk and hist + M + 1 > spec.index_topk:
        with scope("attn.index"):
            def scores(keys):
                return index_scores(q.iq[:, None], q.iw[:, None], keys)[:, 0]

            if indexer is None:
                old = scores(gather_pages_folded(i_cache, layer,
                                                 page_table)[0])
            else:
                old = indexer(q.iq, q.iw, i_cache, layer, page_table,
                              hist_lens)
            score = jnp.concatenate([old, scores(i_win[0]), scores(i_self)],
                                    axis=-1)
            chosen = select_topk(score, seen, spec.index_topk)
    # The places XLA scores itself: the window's columns and the self token,
    # and without a reader the gathered history ahead of them.
    parts = (e_win[0], e_self)
    if reader is None:
        with scope("attn.kv_gather"):
            parts = (gather_pages_folded(e_cache, layer, page_table)[0],
                     *parts)
    kept = jnp.split(chosen, [hist, hist + M], axis=-1)[-len(parts):]
    with scope("attn.core"):
        wk, sk = _weight(q.wk_b, nh)
        wv, sv = _weight(q.wv_b, nh)
        nope = q.nope if sk is None else (
            q.nope.astype(jnp.float32) * sk).astype(q.nope.dtype)
        qa = jnp.einsum("bhd,rhd->bhr", nope, wk,
                        preferred_element_type=jnp.bfloat16)
        width = e_cache.shape[-1]
        qe = jnp.concatenate(
            [qa, q.rope, jnp.zeros((b, nh, width - r - q.rope.shape[-1]),
                                   qa.dtype)], axis=-1)      # [B, Nh, width]
        # The places' scores are never joined: a softmax over their
        # concatenation copies [B, Nh, bucket] float32 scores twice more
        # than the running maximum and sum below (PERF.md section 6, PR 34).
        scores = [jnp.where(keep[:, None, :], jnp.einsum(
            "bhe,bke->bhk", qe, e, preferred_element_type=jnp.float32)
            * spec.attn_scale, -1e30) for keep, e in zip(kept, parts)]
        tops = [jnp.max(sc, axis=-1, initial=-1e30) for sc in scores]
        if reader is not None:
            ctx_h, top_h, total_h = reader(
                qe, e_cache, layer, page_table, hist_lens,
                chosen[:, :hist], spec.attn_scale, r)
            tops.append(top_h)
        top = functools.reduce(jnp.maximum, tops)
        weights = [jnp.exp(sc - top[..., None]) for sc in scores]
        total = sum(jnp.sum(w, axis=-1) for w in weights)
        # Over the whole entry, the latent cut out of the SUM: a slice of
        # the gathered entries would be a copy of them.
        ctx = sum(jnp.einsum("bhk,bke->bhe", w.astype(qe.dtype), e,
                             preferred_element_type=jnp.float32)
                  for w, e in zip(weights, parts))[..., :r]
        if reader is not None:
            w_h = jnp.exp(top_h - top)
            total = total + total_h * w_h
            ctx = ctx + ctx_h * w_h[..., None]
        ctx = ctx / total[..., None]
        out = jnp.einsum("bhr,rhd->bhd", ctx.astype(qe.dtype), wv,
                         preferred_element_type=jnp.float32)
        if sv is not None:
            out = out * sv
        out = out.astype(qe.dtype).reshape(b, -1)
    rows = (jnp.ones((b,), jnp.float32) if live is None
            else live.astype(jnp.float32))
    counts = jnp.stack([jnp.sum(jnp.sum(chosen, -1) * rows),
                        jnp.sum(jnp.sum(seen, -1) * rows)])
    return out, counts


def latent_block_attention(q: LatentQuery, e_cache: jax.Array,
                           layer: jax.Array, page_table: jax.Array,
                           hist_lens: jax.Array, e_win: jax.Array,
                           win_keep: jax.Array, e_blk: jax.Array,
                           spec: ModelSpec, live: jax.Array | None = None,
                           backends: Backends = XLA, lo: int = 0,
                           scoped: bool = True):
    """Attention of a latent block WITHOUT an indexer for a block of S
    query positions a row (a verify step's chained token and its drafts; a
    prediction module's inputs), in the absorbed form of
    ``latent_window_attention``: every query attends every key.

    q's leaves [B, S, ...]; e_cache [L, 1, P, page, width]; the row's keys
    are the pool's slots ``lo`` to hist_lens - 1 of ``layer`` (``lo`` 1 for
    a prediction module's layer, whose slot j holds the entry of position
    j - 1 and whose slot 0 holds nothing), the window's committed columns
    e_win [B, W, width] where win_keep [B, W] says so (a rejected draft's
    column is not one), and the block's own entries e_blk [B, S, width],
    causal. Who reads the pool (``backends.block_reader``):
    attention.latent_block_pallas bound by the runner's record (one walk of
    the row's live pages for all S x Nh query rows, no mask operand), or
    None for XLA's gather of the whole bucket.
    ``scoped`` False draws no scope (a module's attention stays in
    ``mtp``). Returns (attention [B, S, Nh * v_head_dim], float32 [2]: the
    ``live`` rows' keys in context, counted ONCE a step whatever S, twice
    over: attended and in context are the same number here)."""
    b, s = q.nope.shape[:2]
    nh, r = spec.num_heads, spec.kv_lora_rank
    page, width = e_cache.shape[3], e_cache.shape[-1]
    W = e_win.shape[1]
    sc = scope if scoped else (lambda _name: contextlib.nullcontext())
    reader = backends.block_reader()
    parts = [e_win, e_blk]
    kept = [jnp.broadcast_to(win_keep[:, None, :], (b, s * nh, W)),
            jnp.broadcast_to((jnp.arange(s * nh)[:, None] // nh
                              >= jnp.arange(s)[None, :])[None],
                             (b, s * nh, s))]
    if reader is None:
        with sc("attn.kv_gather"):
            parts.insert(0, gather_pages_folded(e_cache, layer,
                                                page_table)[0])
        pos = jnp.arange(page_table.shape[1] * page)[None, :]
        old = (pos >= lo) & (pos < hist_lens[:, None])
        kept.insert(0, jnp.broadcast_to(old[:, None, :],
                                        (b, s * nh, old.shape[1])))
    with sc("attn.core"):
        wk, sk = _weight(q.wk_b, nh)
        wv, sv = _weight(q.wv_b, nh)
        nope = q.nope if sk is None else (
            q.nope.astype(jnp.float32) * sk).astype(q.nope.dtype)
        qa = jnp.einsum("bshd,rhd->bshr", nope, wk,
                        preferred_element_type=jnp.bfloat16)
        qe = jnp.concatenate(
            [qa, q.rope, jnp.zeros((b, s, nh, width - r - q.rope.shape[-1]),
                                   qa.dtype)], axis=-1)
        qe = qe.reshape(b, s * nh, width)
        scores = [jnp.where(keep, jnp.einsum(
            "bhe,bke->bhk", qe, e, preferred_element_type=jnp.float32)
            * spec.attn_scale, -1e30) for keep, e in zip(kept, parts)]
        tops = [jnp.max(sc_, axis=-1, initial=-1e30) for sc_ in scores]
        if reader is not None:
            ctx_h, top_h, total_h = reader(
                qe, e_cache, layer, page_table, hist_lens,
                spec.attn_scale, r, lo)
            tops.append(top_h)
        top = functools.reduce(jnp.maximum, tops)
        weights = [jnp.exp(sc_ - top[..., None]) for sc_ in scores]
        total = sum(jnp.sum(w, axis=-1) for w in weights)
        ctx = sum(jnp.einsum("bhk,bke->bhe", w.astype(qe.dtype), e,
                             preferred_element_type=jnp.float32)
                  for w, e in zip(weights, parts))[..., :r]
        if reader is not None:
            w_h = jnp.exp(top_h - top)
            total = total + total_h * w_h
            ctx = ctx + ctx_h * w_h[..., None]
        ctx = (ctx / total[..., None]).reshape(b, s, nh, r)
        out = jnp.einsum("bshr,rhd->bshd", ctx.astype(qe.dtype), wv,
                         preferred_element_type=jnp.float32)
        if sv is not None:
            out = out * sv
        out = out.astype(qe.dtype).reshape(b, s, -1)
    rows = (jnp.ones((b,), jnp.float32) if live is None
            else live.astype(jnp.float32))
    keys = jnp.sum((jnp.maximum(hist_lens - lo, 0)
                    + jnp.sum(win_keep, axis=-1) + 1) * rows)
    return out, jnp.stack([keys, keys])


def mtp_leaves(layers: dict, module: int = 0) -> dict:
    """The leaves of prediction module ``module`` out of
    ``params["layers"]`` (``MTP_PREFIX``), under the names its block and
    ``mtp_block`` read."""
    return {k[len(MTP_PREFIX):]: jax.tree.map(lambda a: a[module], v)
            for k, v in layers.items() if k.startswith(MTP_PREFIX)}


def mtp_block(params: Params, spec: ModelSpec, hidden: jax.Array,
              next_tokens: jax.Array, cos: jax.Array, sin: jax.Array,
              attend, live: jax.Array | None = None,
              backends: Backends = XLA):
    """The prediction module over positions whose NEXT token is known:
    x_i = [RMS_e(Emb(t_{i+1})) ; RMS_h(h_i)] W_eh, then one whole block of
    the model's kind (its own latent entries; ``attend`` reads them), at
    the rope position of h_i. ``hidden`` [..., H]: the model's output
    after its final norm; ``next_tokens`` [...]. Returns (y [..., H], the
    block's fresh entries, what the block counted: transformer_block's
    dict): the draft of
    t_{i+2} is ``mtp_logits(y_i)``. No scope inside but the expert layer's
    sub-scopes: the caller draws ``mtp`` around it."""
    lp = mtp_leaves(params["layers"])
    eps = spec.rms_norm_eps
    emb = rms_norm(embed_lookup(params["embed"], next_tokens), lp["e_norm"],
                   eps)
    x = mm(jnp.concatenate([emb, rms_norm(hidden, lp["h_norm"], eps)], -1),
           lp["w_eh"], "...i,ih->...h")
    y, k, _, counts = transformer_block(
        x, lp, spec, cos, sin, attend, scoped=False, live=live,
        backends=backends)
    return y, k, counts


def mtp_logits(params: Params, spec: ModelSpec, y: jax.Array) -> jax.Array:
    """Draft logits [..., V] of the module's output y [..., H]: its own
    norm, the model's own head."""
    lp = mtp_leaves(params["layers"])
    x = rms_norm(y, lp["head_norm"], spec.rms_norm_eps)
    return lm_logits(x.reshape(-1, x.shape[-1]), params, spec).reshape(
        *y.shape[:-1], -1)


def mtp_prefill(params: Params, spec: ModelSpec, k_cache: jax.Array,
                hidden: jax.Array, tokens: jax.Array, positions: jax.Array,
                seq_lens: jax.Array, next_token: jax.Array,
                hist: tuple | None = None, backends: Backends = XLA):
    """The prediction module over a prefill chunk: fills its entries for
    every position of the chunk (the next token is the chunk's own next,
    and ``next_token`` [B] after the last valid one: the prompt's next
    token, or the token just sampled) and drafts after the last.

    The entry of position i is kept at slot i + 1 of the module's layer
    (pool layer ``spec.num_layers``), the slot of t_{i+1}, so that a
    page's content is decided by the tokens its hash covers (a cached
    prefix drafts as cold). hidden [B, S, H] the model's normed output.
    ``hist`` (hist_table [B, Ph], hist_lens [B], h_prev [B, H]): the row's
    earlier pages and the model's normed output at the position before the
    chunk (what the page before it left in the runner's ``mtp_hidden``):
    that position's entry, which belongs at the chunk's first slot and
    needs the chunk's first token, is made here with the chunk's own.
    Returns (blocks [1, 1, B * S / page, page, width] for the chunk's
    slots; the last valid position's entry [B, width], which belongs at
    chunk slot seq_lens; draft tokens [B])."""
    b, s = tokens.shape
    page, width = k_cache.shape[3], k_cache.shape[4]
    layer = jnp.asarray(spec.num_layers, jnp.int32)
    last = jnp.maximum(seq_lens - 1, 0)
    nxt = jnp.where(jnp.arange(s)[None, :] == last[:, None],
                    next_token[:, None],
                    jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1))
    valid = jnp.arange(s)[None, :] < seq_lens[:, None]
    mod_hist = None
    if hist is not None:
        hist_table, hist_lens, h_prev = hist
        # One position more, ahead of the chunk's: start - 1, whose next
        # token is the chunk's first.
        hidden = jnp.concatenate([h_prev[:, None], hidden], axis=1)
        nxt = jnp.concatenate([tokens[:, :1], nxt], axis=1)
        positions = jnp.concatenate([positions[:, :1] - 1, positions], axis=1)
        valid = jnp.concatenate([jnp.ones((b, 1), bool), valid], axis=1)
        # Slots 1 .. start - 1 hold the entries of positions 0 .. start - 2.
        old = gather_pages_folded(k_cache, layer, hist_table)[0][:, 1:]
        mod_hist = (old, old[..., :0], hist_lens - 1)
    cos, sin = spec_rope_tables(spec, positions)

    def attend(q, k, v, kind):
        return latent_prefill_attention(q, k, v, positions, valid, spec,
                                        hist=mod_hist)

    y, k_new, _ = mtp_block(params, spec, hidden, nxt, cos, sin, attend,
                            backends=backends)
    e = k_new[:, :, 0]                                   # [B, S (+ 1), w]
    if hist is None:
        # Slot 0 holds no entry (no position before the first).
        e = jnp.concatenate([jnp.zeros_like(e[:, :1]), e], axis=1)
        y = jnp.concatenate([y[:, :1], y], axis=1)
    # Now e[:, j] belongs at chunk slot j: the entry of position j - 1.
    blocks = e[:, :s].reshape(1, 1, b * (s // page), page, width)
    e_last = jnp.take_along_axis(e, last[:, None, None] + 1, axis=1)[:, 0]
    y_last = jnp.take_along_axis(y, last[:, None, None] + 1, axis=1)[:, 0]
    draft = jnp.argmax(mtp_logits(params, spec, y_last), axis=-1)
    return blocks, e_last, draft.astype(jnp.int32)


def decode_verify_step(params: Params, spec: ModelSpec, k_cache: jax.Array,
                       k_buf: jax.Array, win_keep: jax.Array,
                       tokens: jax.Array,
                       positions: jax.Array, page_table: jax.Array,
                       hist_lens: jax.Array, live: jax.Array,
                       backends: Backends = XLA):
    """One verify step INSIDE a drafting window: S = k + 1 tokens a slot
    (the chained token and its drafts) through the model at once, one read
    of the weights for S positions. A latent block without an indexer.

    tokens / positions / live [B, S] (``live``: the positions that count);
    k_buf [L', B, W, width] the window's columns (the model's layers
    first), a layer's slab [B, W, width] read as it lies: the order the
    chip keeps the buffer in whatever order the program states (PERF.md
    section 6, PR 47), win_keep [B, W] which of them hold a committed token;
    hist_lens [B] cache-resident tokens.
    Returns (hidden [B, S, H] after the final norm, logits [B, S, V],
    entries [L, B, S, 1, width], counts: "attn" the key counts [L, 2],
    "moe" the expert layers' ``moe_load_stats``)."""
    b, s = tokens.shape
    with scope("embed"):
        x = embed_lookup(params["embed"], tokens)
    with scope("attn.qkv"):
        cos, sin = spec_rope_tables(spec, positions)
    L = spec.num_layers

    def layer_fn(x, scan_in):
        lp, layer = scan_in

        def attend(q, k, v, kind):
            e_win = jax.lax.dynamic_index_in_dim(k_buf, layer, axis=0,
                                                 keepdims=False)
            return latent_block_attention(
                q, k_cache, layer, page_table, hist_lens, e_win, win_keep,
                k[:, :, 0], spec, live[:, 0], backends)

        x, k, _, counts = transformer_block(
            x, lp, spec, cos, sin, attend, live=live, backends=backends)
        return x, (k, counts)

    x, ys = scan_layers(
        layer_fn, x, (params["layers"], jnp.arange(L)), spec,
        whole_experts=expert_product(b * s, backends) != "masked")
    with scope("lm_head"):
        hidden = norm(x, params["final_norm"], spec)
        logits = lm_logits(hidden.reshape(b * s, -1), params, spec)
    return (hidden, logits.reshape(b, s, -1), *ys)


# ---------------------------------------------------------------------------
# The block, once
# ---------------------------------------------------------------------------

def layer_kind(spec: ModelSpec, layer) -> tuple | None:
    """(rope_on, windowed) of layer ``layer`` (a traced index) as traced
    booleans, or None where every layer is alike (RoPE, full attention):
    such a model's programs carry no trace of the pattern."""
    if not spec.has_layer_pattern:
        return None
    L = spec.num_layers
    rope = jnp.asarray(spec.rope_layout or (1,) * L, bool)
    window = jnp.asarray(spec.sliding_window_layout or (0,) * L, bool)
    return rope[layer], window[layer]


def window_reach(spec: ModelSpec, kind: tuple | None):
    """How far back a query of this layer sees, for a mask ``i - reach <
    j``: the window in a window layer, more than any context in a full
    one; None where no layer has a window."""
    if kind is None or not spec.sliding_window:
        return None
    return jnp.where(kind[1], spec.sliding_window, jnp.int32(2 ** 30))


def window_lo(spec: ModelSpec, kind: tuple | None, positions: jax.Array):
    """The first position the query at ``positions`` [B] still sees in this
    layer (0 in a full layer); None where no layer has a window."""
    reach = window_reach(spec, kind)
    if reach is None:
        return None
    return jnp.maximum(positions - reach + 1, 0)


def transformer_block(x: jax.Array, lp: dict, spec: ModelSpec,
                      cos: jax.Array, sin: jax.Array, attend,
                      kind: tuple | None = None, ll: dict | None = None,
                      ids: jax.Array | None = None, scoped: bool = True,
                      live: jax.Array | None = None,
                      backends: Backends = XLA):
    """One layer, for every forward program: whole-prompt prefill,
    with-history prefill, the single decode step and the decode window
    under either attention backend, the n-gram verify step, embeddings
    and the pipelined prefill's stage. x [B,H] or [B,S,H] is the residual
    stream as it enters the layer; ``attend(q, k, v, kind)`` is the path's
    attention over split heads (it owns its scopes and returns [..., Nh*D]);
    ``kind`` is ``layer_kind`` of this layer. Returns (x, k, v, counts):
    k/v the layer's fresh keys and values, counts what the layer counted
    under the keys the host's table knows (runtime/flight.py COUNTS):
    "moe", ``moe_load_stats`` of the ``live`` rows where asked for (routed
    blocks), "attn", the keys where ``attend`` returns (attention, key
    counts); {} for a layer that counts nothing.
    ``backends``: see ``ffn_block``.

    The latent block (``spec.latent``) differs in the projections ahead of
    ``attend`` alone: q is a ``LatentQuery``, k the token's latent entry
    and v its index key (what the two pools hold)."""
    sc = scope if scoped else (lambda _name: contextlib.nullcontext())
    d = spec.head_dim
    with sc("attn.qkv"):
        h = norm(x, lp["input_norm"], spec)
        if x.dtype != jnp.bfloat16:     # a float32 stream (scan_passes)
            h = h.astype(jnp.bfloat16)
        if spec.latent:
            cq, nope, rope, k = latent_qkv(h, lp, spec, cos, sin)
        else:
            q = mm(h, lp["wq"], "...h,hd->...d")
            k = mm(h, lp["wk"], "...h,hd->...d")
            v = mm(h, lp["wv"], "...h,hd->...d")
            if ll is not None:
                q, k, v = qkv_lora(q, k, v, h, ll, ids)
            if spec.qkv_bias:
                q = q + lp["bq"]
                k = k + lp["bk"]
                v = v + lp["bv"]
            q = _split_heads(q, spec.num_heads, d)
            k = _split_heads(k, spec.num_kv_heads, d)
            v = _split_heads(v, spec.num_kv_heads, d)
            if kind is not None:
                # NoPE layers: the identity rotation (x*1 - y*0 is exact).
                cos = jnp.where(kind[0], cos, 1.0)
                sin = jnp.where(kind[0], sin, 0.0)
            q = apply_rope(q, cos, sin, spec.rope_interleaved)
            k = apply_rope(k, cos, sin, spec.rope_interleaved)
    if spec.latent:
        # What the token leaves in the cache is the entry (k) and the
        # index key (v); the query carries what attend selects by.
        if spec.index_topk:
            with sc("attn.index"):
                iq, iw, v = index_qk(h, cq, lp, spec, cos, sin)
        else:               # no indexer: the second pool has no width
            iq = iw = None
            v = k[..., :0]
        q = LatentQuery(nope, rope, iq, iw, lp["wk_b"], lp["wv_b"])
    attn = attend(q, k, v, kind)
    counts = {}
    if isinstance(attn, tuple):     # the latent window step counts its keys
        attn, counts["attn"] = attn
    with sc("attn.out"):
        proj = mm(attn, lp["wo"], "...d,dh->...h")
        if ll is not None:
            proj = proj + lora_delta(attn, ll["wo"], ids)
        if spec.sandwich_norm:
            proj = norm(proj, lp["attn_out_gain"][..., 0], spec)
        x_in, x = x, x + proj
    with sc("mlp"):
        # A parallel block's feed-forward reads the norm attention read,
        # and its output joins the same residual sum.
        h2 = h if spec.parallel_block else norm(x, lp["post_attn_norm"], spec)
        if h2.dtype != jnp.bfloat16:
            h2 = h2.astype(jnp.bfloat16)
        router_in = x_in if spec.moe_router_input == "layer_input" else None
        out = ffn_block(h2, lp, spec, ll, ids, router_in=router_in,
                        live=live if spec.num_experts else None,
                        backends=backends)
        if isinstance(out, tuple):
            out, counts["moe"] = out
        if spec.sandwich_norm:
            out = norm(out, lp["mlp_out_gain"][..., 0], spec)
        x = x + out
    return x, k, v, counts


def scan_layers(layer_fn, x: jax.Array, xs, spec: ModelSpec,
                whole_experts: bool = False):
    """``jax.lax.scan(layer_fn, x, xs)`` over the layers, where ``xs`` is
    ``params["layers"]`` or a tuple that starts with it and goes on with
    arrays stacked over ALL layers (the layer index, a window's buffers).
    A model with leading dense layers (``first_k_dense``) is two scans: the
    dense layers' leaves (``DENSE_PREFIX``, under the names the layer
    reads) with the first rows of the other arrays, then the rest; what
    both scans give a layer (k, v, and in a dict of counts the keys) is
    joined along the layer axis, what only the expert layers give (their
    load) follows.

    ``whole_experts`` (the caller's layers take a kernel of
    engine/experts.py: ``expert_product`` is not "masked"): the
    EXPERT_LEAVES are not sliced a layer; the
    layer reads them as ``LayerOf`` (the whole stack and its index), which
    the kernel's index maps follow. Sliced ahead of a custom call a layer's
    experts are COPIED (126 MB a matrix of 64 x 2,560 x 768; XLA fuses such
    a slice into its own products alone)."""
    def scan(fn, x, xs):
        layers = xs if isinstance(xs, dict) else xs[0]
        sliced, stacks = (whole_expert_leaves(layers) if whole_experts
                          and isinstance(layers, dict) else (layers, {}))
        if not stacks:
            return jax.lax.scan(fn, x, xs)

        def body(x, scan_in):
            rest, i = scan_in
            lp = rest if isinstance(xs, dict) else rest[0]
            lp = {**lp, **layer_of(stacks, i)}
            return fn(x, lp if isinstance(xs, dict) else (lp, *rest[1:]))

        n = jax.tree.leaves(stacks)[0].shape[0]
        return jax.lax.scan(
            body, x, (sliced if isinstance(xs, dict) else (sliced, *xs[1:]),
                      jnp.arange(n)))

    dense = spec.first_k_dense
    if spec.mtp_layers:
        # A prediction module's leaves (a stack of its own, MTP_PREFIX) are
        # no layer of the model: mtp_leaves hands them to mtp_block.
        strip = lambda d: {k: v for k, v in d.items()  # noqa: E731
                           if not k.startswith(MTP_PREFIX)}
        xs = strip(xs) if isinstance(xs, dict) else (strip(xs[0]), *xs[1:])
    if not dense:
        return scan(layer_fn, x, xs)
    layers, others = (xs, None) if isinstance(xs, dict) else (xs[0], xs[1:])
    first = {k[len(DENSE_PREFIX):]: v for k, v in layers.items()
             if k.startswith(DENSE_PREFIX)}
    rest = {k: v for k, v in layers.items() if not k.startswith(DENSE_PREFIX)}
    if others is not None:
        first = (first, *(a[:dense] for a in others))
        rest = (rest, *(a[dense:] for a in others))
    x, ys_first = jax.lax.scan(layer_fn, x, first)
    x, ys_rest = scan(layer_fn, x, rest)
    def join(a, b):
        if isinstance(b, dict):
            return {k: jnp.concatenate([a[k], v]) if k in a else v
                    for k, v in b.items()}
        return jnp.concatenate([a, b])

    return x, tuple(join(a, b) for a, b in zip(ys_first, ys_rest))


def scan_passes(layer_fn, x: jax.Array, xs, spec: ModelSpec,
                final_norm: jax.Array, live: jax.Array | None = None,
                whole_experts: bool = False):
    """``scan_layers`` once a PASS of a looped stack (``spec.loop_passes``;
    with one pass exactly ``scan_layers``: such a model's programs carry no
    trace of the loop). Every pass scans the SAME ``params["layers"]``; the
    arrays that ``xs`` stacks behind them (the layer index, a window's
    buffers) are stacked over the (pass, layer) pairs, pool layer ``t *
    num_layers + l``, and a pass takes its own rows of them, so a pass
    reads the K and V of its own earlier visits alone. Between two passes
    the model's final norm (scope ``loop.norm``): its output enters the
    next pass, and the last pass's goes to the caller, whose own final norm
    ahead of the head is the one after the last pass. What the layers give
    (k, v and their counts) comes back stacked over the pairs, in the
    pool's order. ``live`` [B] (a window's step): the counts gain "loop",
    a pass a row: (passes the live rows took, the live rows themselves:
    runtime/flight.py COUNTS), counted where the passes run."""
    T = spec.loop_passes
    if T == 1:
        return scan_layers(layer_fn, x, xs, spec,
                           whole_experts=whole_experts)
    L = spec.num_layers
    # The residual stream rides the passes in float32: under sandwich norms
    # every sublayer adds a vector of unit rms to a stream whose rms grows
    # to ten within a pass, and a bfloat16 stream drops three bits of each
    # of 2 x 192 additions (what the layers read of it is bfloat16).
    stream, x = x.dtype, x.astype(jnp.float32)
    layers, others = (xs, None) if isinstance(xs, dict) else (xs[0], xs[1:])

    def one_pass(x, scan_in):
        t, rows = scan_in
        with scope("loop.norm"):
            # (The norm ahead of the first pass is computed and dropped:
            # the embedding enters it as it is.)
            x = jnp.where(t > 0, norm(x, final_norm, spec).astype(x.dtype),
                          x)
        x, ys = scan_layers(layer_fn, x,
                            layers if others is None else (layers, *rows),
                            spec, whole_experts=whole_experts)
        if live is not None:
            n = jnp.sum(live, dtype=jnp.float32)
            ys = (*ys[:-1], {**ys[-1], "loop": jnp.stack(
                [n, jnp.where(t == 0, n, 0.0)])})
        return x, ys

    rows = () if others is None else tuple(
        a.reshape(T, L, *a.shape[1:]) for a in others)
    x, ys = jax.lax.scan(one_pass, x, (jnp.arange(T), rows))
    # [T, L, ...] -> [T * L, ...]; a pass's own count stays a row a pass.
    flat = lambda a: a.reshape(T * L, *a.shape[2:])  # noqa: E731
    return x.astype(stream), tuple(
        {k: v if k == "loop" else flat(v) for k, v in y.items()}
        if isinstance(y, dict) else flat(y) for y in ys)


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------


def prefill_forward(params: Params, spec: ModelSpec,
                    k_cache: jax.Array, v_cache: jax.Array,
                    tokens: jax.Array, positions: jax.Array,
                    page_table: jax.Array, seq_lens: jax.Array,
                    sp_shard: bool = False, ring_mesh=None,
                    x_embeds: jax.Array | None = None,
                    embeds_mask: jax.Array | None = None,
                    lora: dict | None = None,
                    adapter_ids: jax.Array | None = None,
                    backends: Backends = XLA, defer: bool = False,
                    ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Process prompt chunks and write K/V into pages.

    ``defer`` (a model with a prediction module, whose entries join the
    commit once the next token is sampled): the caches come back as they
    were and a fourth value holds what the caller commits, (the normed
    hidden states [B,S,H], k_blocks, v_blocks, flat_pages).

    tokens/positions [B,S] (S = bucket, multiple of page_size), page_table
    [B, S//page_size] (pages covering THIS chunk), seq_lens [B] (valid token
    counts). With sp_shard (requires tracing under the runner's mesh), the
    SEQUENCE axis of activations is sharded over the "sp" mesh axis —
    all-to-all context parallelism: queries stay sequence-sharded, XLA
    gathers K/V, and the quadratic score tensor is sp-sharded, which is
    what lets long-context prefill fit (SURVEY §5.7; ring attention is the
    bandwidth optimization path). Returns (last_token_logits [B,V],
    k_cache, v_cache).
    """
    b, s = tokens.shape
    d = spec.head_dim
    page = k_cache.shape[3]
    with scope("embed"):
        x = embed_lookup(params["embed"], tokens)  # [B,S,H]
        if x_embeds is not None:
            # Multimodal spans: encoder-produced embeddings replace the token
            # table's rows wherever the mask is set (the placeholder ids
            # under the span never reach the model).
            x = jnp.where(embeds_mask[..., None], x_embeds.astype(x.dtype), x)
        if sp_shard:
            x = jax.lax.with_sharding_constraint(x, P(None, "sp", None))
    with scope("attn.qkv"):
        cos, sin = spec_rope_tables(spec, positions)
    valid = jnp.arange(s)[None, :] < seq_lens[:, None]

    patterned = spec.has_layer_pattern

    def attend(q, k, v, kind):
        if spec.latent:     # owns its scopes: attn.index, attn.core
            return latent_prefill_attention(q, k, v, positions, valid, spec)
        with scope("attn.core"):
            if ring_mesh is not None:
                attn = ring_causal_attention(ring_mesh, q, k, v, positions,
                                             valid, spec.q_per_kv)
            else:
                attn = dense_causal_attention(
                    q, k, v, positions, valid, spec.q_per_kv,
                    reach=window_reach(spec, kind))
            return attn.reshape(b, s, -1)

    def layer_fn(x, scan_in):
        layer = None
        if patterned:  # such a model's scan also carries the layer index
            scan_in, layer = scan_in
        lp, ll = scan_in if lora is not None else (scan_in, None)
        x, k, v, _ = transformer_block(
            x, lp, spec, cos, sin, attend, layer_kind(spec, layer), ll,
            adapter_ids, backends=backends)
        return x, (k, v)

    # Cache writes are deferred out of the scan (ys are fresh allocations —
    # carrying the caches through would rewrite the whole pool per call).
    xs = (params["layers"], lora) if lora is not None else params["layers"]
    if patterned:
        xs = (xs, jnp.arange(spec.num_layers))
    x, (k_new, v_new) = scan_passes(
        layer_fn, x, xs, spec, params["final_norm"],
        whole_experts=expert_product(b * s, backends) != "masked")
    # k_new [L,B,S,Nkv,D] -> page blocks [L,Nkv,B*S/page,page,D]; one
    # in-place scatter per cache covers every layer (of every pass).
    with scope("kv.commit"):
        L = spec.layer_visits
        nkv, (dk, dv) = spec.kv_entry
        k_blocks = (k_new.reshape(L, b * (s // page), page, nkv, dk)
                    .transpose(0, 3, 1, 2, 4))
        v_blocks = (v_new.reshape(L, b * (s // page), page, nkv, dv)
                    .transpose(0, 3, 1, 2, 4))
        flat_pages = page_table.reshape(-1)
        if not defer:
            # scatter_pages quantizes int8 pools in the same fused commit.
            k_cache = scatter_pages(k_cache, k_blocks, flat_pages)
            v_cache = scatter_pages(v_cache, v_blocks, flat_pages)
    with scope("lm_head"):
        x = norm(x, params["final_norm"], spec)
        # Last valid token per sequence.
        last_idx = jnp.maximum(seq_lens - 1, 0)
        x_last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]
        logits = lm_logits(x_last, params, spec)
    if defer:
        return logits, k_cache, v_cache, (x, k_blocks, v_blocks, flat_pages)
    return logits, k_cache, v_cache


def prefill_forward_pipelined(params: Params, spec: ModelSpec,
                              k_cache: jax.Array, v_cache: jax.Array,
                              tokens: jax.Array, positions: jax.Array,
                              page_table: jax.Array, seq_lens: jax.Array,
                              n_stages: int
                              ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """MICROBATCHED pipeline-parallel prefill: GPipe-style fill/drain over
    the "pp" mesh axis, expressed in pure GSPMD (no shard_map).

    The layer-sharded pp path (prefill_forward with P("pp") on the layer
    axis) distributes memory but serializes stages — each stage idles
    while the single batch traverses the other stages' layers. Here the
    batch's ROWS split into ``n_stages`` microbatches that flow through
    the stages concurrently:

    - weights reshape [L, ...] -> [S, L/S, ...] (the pp-sharded L axis
      becomes the stage axis — layout-preserving, one shard per stage);
    - activations live in a stage buffer x[S, mb, s, H] sharded
      P("pp", ...): tick t runs jax.vmap(stage_forward) over the stage
      axis, so GSPMD executes every stage's L/S layers IN PARALLEL on its
      own devices (this is the overlap);
    - between ticks the buffer shifts one stage (jnp.roll on the
      pp-sharded axis lowers to a collective-permute over ICI — the
      artifact to look for in the compiled HLO), stage 0 ingests the next
      microbatch's embeddings, and stage S-1's output drains into the
      result buffer;
    - each tick's fresh K/V lands in a [G, S, ...] buffer indexed by
      (microbatch, stage) with out-of-range (bubble) ticks clamped to a
      discard row; ONE page scatter at the end commits everything, same
      as prefill_forward.

    G = S microbatches -> G+S-1 ticks, bubble fraction (S-1)/(2S-1).
    Rows must divide evenly by n_stages (the runner pads the batch).
    The reference delegates PP to its engines (trtllm main.py:162
    pipeline_parallel_size); this repo IS the engine, so the capability
    is native (round-3 VERDICT missing #4).
    """
    B, s = tokens.shape
    S = n_stages
    G = S  # microbatches
    assert B % G == 0, (B, G)
    mb = B // G
    d = spec.head_dim
    page = k_cache.shape[3]
    L = spec.num_layers
    Ls = L // S
    nkv = spec.num_kv_heads

    # Weights: [L, ...] -> [S, L/S, ...]; the pp-sharded L axis becomes
    # the stage axis (explicit constraint keeps GSPMD from re-sharding).
    def stage_weights(w):
        out = w.reshape(S, Ls, *w.shape[1:])
        return jax.lax.with_sharding_constraint(
            out, P("pp", *([None] * (out.ndim - 1))))

    w_stages = jax.tree.map(stage_weights, params["layers"])

    # Per-microbatch inputs, precomputed: [G, mb, s, ...].
    emb = embed_lookup(params["embed"], tokens).reshape(G, mb, s, -1)
    pos_g = positions.reshape(G, mb, s)
    valid_g = (jnp.arange(s)[None, :]
               < seq_lens[:, None]).reshape(G, mb, s)

    def stage_forward(w, x, pos, valid):
        """L/S layers of ONE stage on one microbatch (the inner loop of
        prefill_forward, minus embed/head)."""
        cos, sin = rope_tables(pos, d, spec.rope_theta)

        def attend(q, k, v, kind):
            return dense_causal_attention(q, k, v, pos, valid,
                                          spec.q_per_kv).reshape(mb, s, -1)

        def layer_fn(x, lp):
            x, k, v, _ = transformer_block(x, lp, spec, cos, sin, attend,
                                           scoped=False)
            return x, (k, v)

        x, (k_new, v_new) = jax.lax.scan(layer_fn, x, w)
        return x, k_new, v_new  # k/v: [L/S, mb, s, nkv, d]

    x0 = jnp.zeros((S, mb, s, emb.shape[-1]), jnp.bfloat16)
    x0 = jax.lax.with_sharding_constraint(x0, P("pp", None, None, None))
    pos0 = jnp.zeros((S, mb, s), positions.dtype)
    val0 = jnp.zeros((S, mb, s), bool)
    # (microbatch, stage) K/V accumulator + a discard row at index G for
    # bubble-tick outputs.
    kbuf0 = jnp.zeros((G + 1, S, Ls, mb, s, nkv, d), k_cache.dtype)
    vbuf0 = jnp.zeros_like(kbuf0)
    xout0 = jnp.zeros((G + 1, mb, s, emb.shape[-1]), jnp.bfloat16)

    def tick(carry, t):
        x_st, pos_st, val_st, kbuf, vbuf, xout = carry
        # Ingest: stage 0 takes microbatch t (clamped; bubble ticks feed
        # stage 0 stale data whose outputs are discarded below).
        g_in = jnp.clip(t, 0, G - 1)
        x_st = x_st.at[0].set(emb[g_in])
        pos_st = pos_st.at[0].set(pos_g[g_in])
        val_st = val_st.at[0].set(valid_g[g_in])
        x_new, k_new, v_new = jax.vmap(stage_forward)(
            w_stages, x_st, pos_st, val_st)
        # Stage s just processed microbatch t - s: scatter its K/V into
        # the (g, s) buffer; bubble outputs land on the discard row G.
        g_of_stage = t - jnp.arange(S)
        g_idx = jnp.where((g_of_stage >= 0) & (g_of_stage < G),
                          g_of_stage, G)
        kbuf = kbuf.at[g_idx, jnp.arange(S)].set(k_new)
        vbuf = vbuf.at[g_idx, jnp.arange(S)].set(v_new)
        # Drain: stage S-1's output is microbatch t-(S-1), complete.
        g_out = t - (S - 1)
        xout = xout.at[jnp.where((g_out >= 0) & (g_out < G), g_out, G)] \
            .set(x_new[S - 1])
        # Shift one stage forward (collective-permute over "pp").
        x_st = jax.lax.with_sharding_constraint(
            jnp.roll(x_new, 1, axis=0), P("pp", None, None, None))
        pos_st = jnp.roll(pos_st, 1, axis=0)
        val_st = jnp.roll(val_st, 1, axis=0)
        return (x_st, pos_st, val_st, kbuf, vbuf, xout), ()

    (_, _, _, kbuf, vbuf, xout), _ = jax.lax.scan(
        tick, (x0, pos0, val0, kbuf0, vbuf0, xout0),
        jnp.arange(G + S - 1))

    # [G, S, L/S, mb, s, nkv, d] -> [L, B*s/page, page, nkv, d] blocks.
    k_new = (kbuf[:G].transpose(1, 2, 0, 3, 4, 5, 6)
             .reshape(L, B, s, nkv, d))
    v_new = (vbuf[:G].transpose(1, 2, 0, 3, 4, 5, 6)
             .reshape(L, B, s, nkv, d))
    k_blocks = (k_new.reshape(L, B * (s // page), page, nkv, d)
                .transpose(0, 3, 1, 2, 4))
    v_blocks = (v_new.reshape(L, B * (s // page), page, nkv, d)
                .transpose(0, 3, 1, 2, 4))
    flat_pages = page_table.reshape(-1)
    # scatter_pages quantizes int8 pools in the same fused commit.
    k_cache = scatter_pages(k_cache, k_blocks, flat_pages)
    v_cache = scatter_pages(v_cache, v_blocks, flat_pages)

    x = xout[:G].reshape(B, s, -1)
    x = norm(x, params["final_norm"], spec)
    last_idx = jnp.maximum(seq_lens - 1, 0)
    x_last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]
    logits = lm_logits(x_last, params, spec)
    return logits, k_cache, v_cache


def decode_forward(params: Params, spec: ModelSpec,
                   k_cache: jax.Array, v_cache: jax.Array,
                   tokens: jax.Array, positions: jax.Array,
                   page_table: jax.Array, seq_lens: jax.Array,
                   backends: Backends = XLA,
                   write_mask: jax.Array | None = None,
                   lora: dict | None = None,
                   adapter_ids: jax.Array | None = None,
                   ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step for the whole slot batch.

    tokens [B], positions [B] (absolute position of the new token), page_table
    [B, maxP], seq_lens [B] (lengths INCLUDING the new token). write_mask [B]
    bool (optional): rows with False scatter their K/V to the reserved
    scratch page 0 instead of their own pages (used by the window loop to
    freeze slots that hit page capacity mid-window). Returns
    (logits [B,V], k_cache, v_cache).
    """
    b = tokens.shape[0]
    d = spec.head_dim
    page = k_cache.shape[3]
    x = embed_lookup(params["embed"], tokens)  # [B,H]
    cos, sin = spec_rope_tables(spec, positions)
    # Target page slot for the new token.
    page_idx = positions // page
    page_off = positions % page
    dest_page = jnp.take_along_axis(page_table, page_idx[:, None], axis=1)[:, 0]
    if write_mask is not None:
        dest_page = jnp.where(write_mask, dest_page, 0)
        page_off = jnp.where(write_mask, page_off, 0)
    attn_fn = kv_attention(backends, window=False)
    # The new token's K/V is NOT written inside the layer loop: attention
    # takes it as an explicit self column (hist_lens = cache-resident
    # length) and one batched scatter below writes all layers at once. The
    # caches therefore never ride the scan as stacked ys — scan ys are
    # freshly allocated each call, which silently rewrote the ENTIRE pool
    # per decode step (50 ms/step at a 3 GB pool vs ~1.5 ms now).
    hist_lens = jnp.maximum(seq_lens - 1, 0)
    L = spec.layer_visits

    def layer_fn(x, scan_in):
        if lora is not None:
            lp, layer, ll = scan_in
        else:
            (lp, layer), ll = scan_in, None

        def attend(q, k, v, kind):
            if spec.latent:     # the window's attention, no window columns
                return latent_window_attention(
                    q, k_cache, v_cache, layer, page_table, hist_lens,
                    k[None, :, :0], v[None, :, :0], jnp.asarray(0, jnp.int32),
                    k, v, spec, backends=backends)[0]
            attn = attn_fn(q, k_cache, v_cache, layer, page_table, hist_lens,
                           k, v, spec.q_per_kv,
                           lo=window_lo(spec, kind, positions))  # [B,Nh,D]
            return attn.reshape(b, -1)

        x, k, v, _ = transformer_block(
            x, lp, spec, cos, sin, attend, layer_kind(spec, layer), ll,
            adapter_ids, scoped=False, backends=backends)
        return x, (k, v)

    xs = ((params["layers"], jnp.arange(L), lora) if lora is not None
          else (params["layers"], jnp.arange(L)))
    x, (k_new, v_new) = scan_passes(
        layer_fn, x, xs, spec, params["final_norm"],
        whole_experts=expert_product(b, backends) != "masked")
    # One in-place scatter: [L,Nkv,B,D] at (dest_page[b], page_off[b]).
    k_cache = scatter_tokens(k_cache, k_new.transpose(0, 2, 1, 3),
                             dest_page, page_off)
    v_cache = scatter_tokens(v_cache, v_new.transpose(0, 2, 1, 3),
                             dest_page, page_off)
    x = norm(x, params["final_norm"], spec)
    logits = lm_logits(x, params, spec)
    return logits, k_cache, v_cache


def decode_window_multi_step(params: Params, spec: ModelSpec,
                             k_cache: jax.Array, v_cache: jax.Array,
                             k_buf: jax.Array, v_buf: jax.Array,
                             wlen: jax.Array, tokens: jax.Array,
                             positions: jax.Array, page_table: jax.Array,
                             hist_lens: jax.Array,
                             lora: dict | None = None,
                             adapter_ids: jax.Array | None = None
                             ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Speculative-verification step INSIDE a window: S tokens per slot
    (the chained token + S-1 n-gram drafts) forwarded TOGETHER — one
    weight read verifies S positions, which is the whole point of
    speculative decoding on an HBM-bound decode (SURVEY §5.7; reference
    delegates spec decode to its engines, protocols.rs:32-56 stats).

    tokens/positions [B,S]; wlen [B] = valid columns already committed to
    the in-window buffer k_buf/v_buf [L,Nkv,B,W,D]; hist_lens [B] =
    cache-resident tokens. Attention per query j: paged history +
    window-buffer cols < wlen + in-block causal (cols <= j).
    Returns (logits [B,S,V], k_new, v_new [L,B,S,Nkv,D])."""
    b, s = tokens.shape
    d = spec.head_dim
    nkv = spec.num_kv_heads
    page = k_cache.shape[3]
    maxp = page_table.shape[1]
    W = k_buf.shape[3]
    with scope("embed"):
        x = embed_lookup(params["embed"], tokens)          # [B,S,H]
    with scope("attn.qkv"):
        cos, sin = spec_rope_tables(spec, positions)
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    L = spec.num_layers

    def layer_fn(x, scan_in):
        if lora is not None:
            lp, layer, kb_l, vb_l, ll = scan_in        # kb_l [Nkv,B,W,D]
        else:
            (lp, layer, kb_l, vb_l), ll = scan_in, None

        def attend(q, k, v, kind):
            # No window mask in the three score blocks: ``kind`` is not
            # read (config.block_refusals: spec_decode with a window layer).
            with scope("attn.core"):
                qg = q.reshape(b, s, nkv, spec.q_per_kv, d)
            # Paged history: the same layer+head-folded fused gather as the
            # single-token step — the [B,S] verify reads the bucketed page
            # table once per layer into the dot's [Nkv,B,L,D] layout, with
            # no materialized per-position (or per-head-transpose) copies.
            with scope("attn.kv_gather"):
                k_all = gather_pages_folded(k_cache, layer, page_table)
                v_all = gather_pages_folded(v_cache, layer, page_table)
            with scope("attn.core"):
                s_hist = jnp.einsum(
                    "bsngd,nbld->bnsgl", qg, k_all,
                    preferred_element_type=jnp.float32) * scale
                lpos = jnp.arange(maxp * page)[None, :]
                s_hist = jnp.where(
                    (lpos < hist_lens[:, None])[:, None, None, None, :],
                    s_hist, -1e30)
                # This window's committed columns (< wlen per slot).
                s_win = jnp.einsum(
                    "bsngd,nbjd->bnsgj", qg, kb_l,
                    preferred_element_type=jnp.float32) * scale
                wvalid = (jnp.arange(W)[None, :]
                          < wlen[:, None])[:, None, None, None, :]
                s_win = jnp.where(jnp.broadcast_to(wvalid, s_win.shape),
                                  s_win, -1e30)
                # In-block causal among the S verify tokens.
                s_blk = jnp.einsum(
                    "bsngd,btnd->bnsgt", qg, k,
                    preferred_element_type=jnp.float32) * scale
                causal = (jnp.arange(s)[:, None] >= jnp.arange(s)[None, :])
                s_blk = jnp.where(causal[None, None, :, None, :], s_blk,
                                  -1e30)
                full = jnp.concatenate([s_hist, s_win, s_blk], axis=-1)
                probs = jax.nn.softmax(full, axis=-1)
                p_hist = probs[..., :maxp * page].astype(q.dtype)
                p_win = probs[..., maxp * page:
                              maxp * page + W].astype(q.dtype)
                p_blk = probs[..., maxp * page + W:].astype(q.dtype)
                out = (jnp.einsum("bnsgl,nbld->bsngd", p_hist, v_all)
                       + jnp.einsum("bnsgj,nbjd->bsngd", p_win, vb_l)
                       + jnp.einsum("bnsgt,btnd->bsngd", p_blk, v))
                return out.reshape(b, s, -1)

        x, k, v, _ = transformer_block(
            x, lp, spec, cos, sin, attend, layer_kind(spec, layer), ll,
            adapter_ids)
        return x, (k, v)

    xs = ((params["layers"], jnp.arange(L), k_buf, v_buf, lora)
          if lora is not None
          else (params["layers"], jnp.arange(L), k_buf, v_buf))
    x, (k_new, v_new) = jax.lax.scan(layer_fn, x, xs)
    with scope("lm_head"):
        x = norm(x, params["final_norm"], spec)
        logits = lm_logits(x.reshape(b * s, -1), params, spec)
    return logits.reshape(b, s, -1), k_new, v_new


def embed_forward(params: Params, spec: ModelSpec, tokens: jax.Array,
                  seq_lens: jax.Array, pooling: str = "last"
                  ) -> jax.Array:
    """Embedding forward: full transformer pass, pooled final hidden
    states (no KV cache — embeddings are single-shot). tokens [B,S]
    (padded), seq_lens [B]. pooling: "last" (final valid token) or
    "mean" (masked mean). Returns L2-normalized [B,H] float32 — the
    engine side of /v1/embeddings (reference embeddings path,
    lib/llm/src/protocols/openai/embeddings*)."""
    b, s = tokens.shape
    d = spec.head_dim
    x = embed_lookup(params["embed"], tokens)
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    cos, sin = spec_rope_tables(spec, positions)
    valid = jnp.arange(s)[None, :] < seq_lens[:, None]
    patterned = spec.has_layer_pattern

    def attend(q, k, v, kind):
        attn = dense_causal_attention(q, k, v, positions, valid,
                                      spec.q_per_kv,
                                      reach=window_reach(spec, kind))
        return attn.reshape(b, s, -1)

    def layer_fn(x, scan_in):
        lp, layer = scan_in if patterned else (scan_in, None)
        x, _, _, _ = transformer_block(x, lp, spec, cos, sin, attend,
                                       layer_kind(spec, layer), scoped=False)
        return x, ()

    xs = ((params["layers"], jnp.arange(spec.num_layers)) if patterned
          else params["layers"])
    x, _ = jax.lax.scan(layer_fn, x, xs)
    x = norm(x, params["final_norm"], spec).astype(
        jnp.float32)
    if pooling == "mean":
        m = valid[..., None].astype(jnp.float32)
        pooled = (x * m).sum(axis=1) / jnp.maximum(m.sum(axis=1), 1.0)
    else:
        last = jnp.maximum(seq_lens - 1, 0)
        pooled = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    return pooled / jnp.maximum(
        jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12)


def decode_window_step(params: Params, spec: ModelSpec,
                       k_cache: jax.Array, v_cache: jax.Array,
                       k_buf: jax.Array, v_buf: jax.Array, m: jax.Array,
                       tokens: jax.Array, positions: jax.Array,
                       page_table: jax.Array, hist_lens: jax.Array,
                       backends: Backends = XLA, lora: dict | None = None,
                       adapter_ids: jax.Array | None = None,
                       live: jax.Array | None = None) -> tuple:
    """One decode step INSIDE an M-step window: the caches are read-only
    (gathered), this window's earlier tokens come from k_buf/v_buf
    [L,Nkv,B,M,D], and the step's fresh K/V is returned ([L,B,Nkv,D]) for
    the caller to append to the buffer — no cache writes here at all.

    hist_lens [B]: tokens cache-resident BEFORE the window (fixed across
    the window). Returns (logits [B,V], k_new, v_new, counts): what the
    layers counted, a layer a row, under transformer_block's keys: with
    ``live`` [B] (bool, a routed block's rows that count) "moe", the
    expert layers' ``moe_load_stats`` [L, 3]; a latent block's "attn", its
    key counts [L, 2]; a looped stack's "loop", a pass a row
    (``scan_passes``: [passes, 2]); {} for a block that counts nothing.
    """
    b = tokens.shape[0]
    d = spec.head_dim
    with scope("embed"):
        x = embed_lookup(params["embed"], tokens)
    with scope("attn.qkv"):
        cos, sin = spec_rope_tables(spec, positions)
    attn_fn = kv_attention(backends, window=True)
    L = spec.layer_visits

    def layer_fn(x, scan_in):
        if lora is not None:
            lp, layer, kb_l, vb_l, ll = scan_in
        else:
            (lp, layer, kb_l, vb_l), ll = scan_in, None

        def attend(q, k, v, kind):
            if spec.latent:     # owns its scopes; counts the live rows' keys
                return latent_window_attention(
                    q, k_cache, v_cache, layer, page_table, hist_lens, kb_l,
                    vb_l, m, k, v, spec, live, backends=backends)
            with scope("attn.core"):
                attn = attn_fn(q, k_cache, v_cache, layer, page_table,
                               hist_lens, kb_l, vb_l, m, k, v, spec.q_per_kv,
                               lo=window_lo(spec, kind, positions))
                return attn.reshape(b, -1)

        x, k, v, counts = transformer_block(
            x, lp, spec, cos, sin, attend, layer_kind(spec, layer), ll,
            adapter_ids, live=live, backends=backends)
        return x, (k, v, counts)

    xs = ((params["layers"], jnp.arange(L), k_buf, v_buf, lora)
          if lora is not None
          else (params["layers"], jnp.arange(L), k_buf, v_buf))
    x, ys = scan_passes(
        layer_fn, x, xs, spec, params["final_norm"],
        live=live if spec.loop_passes > 1 else None,
        whole_experts=expert_product(b, backends) != "masked")
    with scope("lm_head"):
        x = norm(x, params["final_norm"], spec)
        logits = lm_logits(x, params, spec)
    return (logits, *ys)
