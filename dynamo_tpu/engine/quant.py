"""Weight-only int8 quantization with bf16 compute.

The decode hot path is HBM-bandwidth-bound (one full weight read per
step: 76 % of the Qwen cell's step and 82 % of SmallThinker's, PERF.md
section 5, my chip run, PR 29), so halving weight bytes both
doubles the decode ceiling and is what fits full Llama-3-8B (16 GB bf16)
on a single 16 GB v5e chip beside its KV cache (round-3 VERDICT missing
#7; the reference ecosystem's own baseline workload is a quantized 70B,
benchmarks/llm/perf.sh:18-29).

Scheme: symmetric per-output-channel int8. A weight W[..., in, out]
stores q = round(W/s) in int8 and s[..., 1, out] in float32;
matmuls run x @ q (int8 operand converted to bf16 in the dot — XLA
fuses the convert into the operand read, so the dequantized matrix is
never materialized) and the [out]-shaped scale multiplies the OUTPUT —
the standard weight-only pattern, MXU stays in bf16.

The embedding table quantizes per-hidden-channel: the token gather reads
int8 rows and scales [H]; the tied LM head contracts over H, so its
scale folds into the activation side ((x*s) @ q.T) — again no
materialized dequant.

QTensor is a NamedTuple, hence a pytree: scan-over-layers slicing,
sharding trees, and device placement all compose without special cases.
Router gates, norms and biases stay bf16 (tiny, accuracy-sensitive).
"""

from __future__ import annotations

from typing import Any, NamedTuple

import numpy as np

from dynamo_tpu.engine.config import DENSE_PREFIX, MTP_PREFIX


class QTensor(NamedTuple):
    """int8 weight + broadcastable scale; a pytree of two leaves."""
    q: Any   # int8 [..., in, out]
    s: Any   # float32 [..., 1, out]


# Layer leaves that quantize (the big matmuls); everything else stays bf16.
_BLOCK_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
               "moe_w_gate", "moe_w_up", "moe_w_down",
               "shared_w_gate", "shared_w_up", "shared_w_down")
# The DeepSeek-V3.2 block's latent projections and its indexer's (the
# indexer's head weights index_w stay bf16 beside the router), and a leading
# dense layer's leaves under their own names (config.DENSE_PREFIX).
_LATENT_KEYS = ("wq_a", "wq_b", "wkv_a", "wk_b", "wv_b", "index_wq_b",
                "index_wk")
# The Nemotron-H block's recurrent layer: its two projections (the
# convolution, A, D, dt's bias and the gated norm stay bf16).
_RECURRENT_KEYS = ("ssm_w_in", "ssm_w_dt", "ssm_w_out",
                   # The delta-rule mixer's low-rank pairs and beta's
                   # projection (the taps, A_log and dt's bias stay bf16).
                   "ssm_w_fa", "ssm_w_fb", "ssm_w_ga", "ssm_w_gb",
                   "ssm_w_beta")
# The output gate of an attention layer over chosen blocks (MiniCPM-SALA).
_GATE_KEYS = ("wz",)
QUANT_LAYER_KEYS = (_BLOCK_KEYS + _LATENT_KEYS + _RECURRENT_KEYS
                    + _GATE_KEYS) + tuple(
    DENSE_PREFIX + key for key in ("wo", "w_gate", "w_up", "w_down")
    + _LATENT_KEYS) + tuple(
    # A prediction module's block (an expert layer of the latent kind) and
    # its projection of [embedding ; hidden] (config.MTP_PREFIX).
    MTP_PREFIX + key for key in ("wo", "w_eh") + _BLOCK_KEYS[7:]
    + _LATENT_KEYS[:5])


def _safe_scale(amax: np.ndarray) -> np.ndarray:
    """amax/127 with two guards: all-zero channels take s=1 (exact
    round trip), and channels near float32-max step s DOWN one ulp when
    the division rounded up — otherwise the saturated code dequantizes
    to 127*s = inf (caught by the max-magnitude edge-case test)."""
    s = (amax / 127.0).astype(np.float32)
    s = np.where(s == 0.0, np.float32(1.0), s)
    with np.errstate(over="ignore"):
        over = ~np.isfinite(np.float32(127.0) * s)
    return np.where(over, np.nextafter(s, np.float32(0.0)), s)


def quantize_weight(w: np.ndarray) -> QTensor:
    """Symmetric per-out-channel int8 over the last axis (reduce over the
    contraction axis -2). Host-side, float32 math."""
    wf = np.asarray(w, np.float32)
    amax = np.max(np.abs(wf), axis=-2, keepdims=True)
    s = _safe_scale(amax)
    q = np.clip(np.rint(wf / s), -127, 127).astype(np.int8)
    return QTensor(q=q, s=s)


def quantize_embedding(w: np.ndarray) -> QTensor:
    """Embedding table [V, H]: per-H-channel scale [1, H] — right for both
    the row gather (scale broadcasts over gathered rows) and the tied head
    (scale folds into the activations before the contraction)."""
    wf = np.asarray(w, np.float32)
    amax = np.max(np.abs(wf), axis=0, keepdims=True)
    s = _safe_scale(amax)
    q = np.clip(np.rint(wf / s), -127, 127).astype(np.int8)
    return QTensor(q=q, s=s)


def quantize_params(params: dict) -> dict:
    """bf16 param pytree -> same tree with QTensor leaves for the big
    matmuls. Operates leaf-by-leaf so peak host memory stays ~one tensor
    above the input tree."""
    layers = dict(params["layers"])
    for key in QUANT_LAYER_KEYS:
        if key in layers:
            layers[key] = quantize_weight(layers[key])
    out = dict(params)
    out["layers"] = layers
    out["embed"] = quantize_embedding(params["embed"])
    if "lm_head" in params:
        out["lm_head"] = quantize_weight(params["lm_head"])
    return out


def weight_dtype_bytes(quant: str | None) -> float:
    """Bytes per weight element for capacity/roofline accounting."""
    return 1.0 if quant == "int8" else 2.0


def random_params_for_timing(spec, seed: int = 7, scale: float = 1.0):
    """Build a (quantized, if spec.quant) param tree with random values
    DIRECTLY on the default device — for benches/profilers only. Host
    init of an 8B model costs ~15 min of host RNG on a small VM; timing
    runs don't care about the values. Shapes come from eval_shape over
    the real init+quantize path, so the tree structure is exactly what
    ModelRunner expects."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine.model import init_params

    def build(key):
        p = init_params(spec, key)
        if spec.quant == "int8":
            # Traceable twin of quantize_params (which is host/numpy).
            def qw(w, emb=False):
                wf = w.astype(jnp.float32)
                amax = jnp.max(jnp.abs(wf), axis=0 if emb else -2,
                               keepdims=True)
                s = jnp.where(amax == 0, 1.0, amax / 127.0)
                return QTensor(q=jnp.clip(jnp.rint(wf / s), -127, 127)
                               .astype(jnp.int8), s=s)

            layers = dict(p["layers"])
            for k in QUANT_LAYER_KEYS:
                if k in layers:
                    layers[k] = qw(layers[k])
            p = dict(p)
            p["layers"] = layers
            p["embed"] = qw(p["embed"], emb=True)
            if "lm_head" in p:
                p["lm_head"] = qw(p["lm_head"])
        return p

    flat, treedef = jax.tree.flatten(jax.eval_shape(build,
                                                    jax.random.key(0)))

    # numpy RNG per leaf: ~2 orders of magnitude faster than jax's CPU
    # threefry for bulk int8 (the values are irrelevant here), and peak
    # memory stays ~one leaf (a single fused jit program materializing
    # every leaf's RNG intermediate OOMed at 8B).
    import ml_dtypes

    rng = np.random.default_rng(seed)
    leaves = []
    for sds in flat:
        if np.issubdtype(sds.dtype, np.integer):
            leaves.append(rng.integers(-127, 128, size=sds.shape,
                                       dtype=np.int8))
        else:
            # ``scale`` ~0 zeroes every float leaf INCLUDING int8
            # dequant scales -> logits ~0 -> greedy emits one constant
            # token: a stand-in for maximally repetitive text in
            # spec-decode benches (verification still runs the full
            # real-shaped math).
            arr = ((rng.standard_normal(sds.shape, dtype=np.float32)
                    * 0.02 + 0.01) * scale)
            if sds.dtype == jnp.bfloat16:
                arr = arr.astype(ml_dtypes.bfloat16)
            else:
                arr = arr.astype(sds.dtype)
            leaves.append(arr)
    return jax.tree.unflatten(treedef, [jnp.asarray(a) for a in leaves])
