"""The plain reference: a decoder-only transformer forward in float32.

This module is the DENSE block's reference and every configuration's default.
A configuration of another block kind names its own (``for_config``:
``benchmark/references/<name>.py``), which brings its layer and, if it has
measured one, its tolerance; ``teacher_forced``, ``judge`` and ``diff_stats``
here are the one definition for all of them.

``jax.numpy`` only, ``default_matmul_precision("highest")``, one layer at a
time over the SAME device-resident parameters the server holds (int8 leaves
dequantised as stored: q * s), no cache, no batching, no paging, nothing of
engine/model.py. It follows the published Qwen2 / Llama block: RMSNorm,
rotary embedding in the rotate-half form, grouped-query causal attention
scaled by 1/sqrt(head_dim), optional QKV bias, SwiGLU, untied or tied head.

What it checks: the logprob the server reported for each generated token
against the logprob this forward gives the same token after the same prefix
(teacher forcing), so greedy near-ties cannot make the two diverge.

The tolerance bounds three statistics of the absolute difference over the 64
tokens of four prompts. The served path computes in bfloat16 (8 bits of
mantissa) with float32 accumulation, the reference in float32 throughout,
and how far that rounding moves a logprob depends on the random model and
has a heavy tail: a few tokens move several times further than most. So the
MEDIAN carries the check. Over seven runs of six seeds on the v5e at 7B int8
(my chip runs, PR 24, review session, ``--probe-faults``):

    nat                      median         root mean square   worst token
    served, bf16 path        0.0047-0.0096  0.0066-0.026       0.015-0.109
    LAST layer left out      0.035-0.213    0.044-0.225        0.080-0.543
    FIRST layer left out     0.67-7.8       0.78-7.8           1.3-8.6

MEDIAN_NATS 0.02 is twice the largest median the bf16 path read and under
0.6 of the smallest a skipped last layer read, so in every one of those
runs the served logprobs pass and both faults fail (``would_pass`` false
seven times of seven, each); the root mean square alone would not separate
them (0.026 against 0.044), and the worst token not at all. RMS_NATS and
WORST_NATS are a little over twice the largest readings of twelve earlier
seeds and of these: they catch a fault in a few tokens (a wrong page or
position for some rows), which moves those by whole nats and the median not
at all. (PR 21 read 0.0013 nat at worst against the program's own bf16
forward on one chip, 0.018 at tp=4.)

What no tolerance here can catch is an int8 KV cache in place of a bf16 one:
its rounding (a 127th of a row's largest value) is of bfloat16's own order.
The same model and seed served with ``--quant-kv int8`` read median 0.0045,
root mean square 0.0067, worst 0.017 nat against this reference, and 0.0049,
0.0066, 0.015 with the bf16 cache (my chip runs, PR 24, call C). run.py
checks the cache's type against the configuration instead.
"""

from __future__ import annotations

import functools
import math
import sys

from benchmark.lib import manifest

MEDIAN_NATS = 0.02
RMS_NATS = 0.06
WORST_NATS = 0.25
#: What a reference module may state for its own block kind (see
#: ``for_config``); these are the three above.
ALLOWED_NATS = {"median": MEDIAN_NATS, "rms": RMS_NATS, "worst": WORST_NATS}


def _dims(spec) -> tuple:
    return (spec.num_heads, spec.num_kv_heads, spec.head_dim,
            float(spec.rms_norm_eps), float(spec.rope_theta),
            bool(spec.qkv_bias))


def plain(leaf):
    """A float32 matrix from a leaf as stored: a (q, s) pair is int8 values
    and their float32 scales."""
    import jax.numpy as jnp
    if hasattr(leaf, "q") and hasattr(leaf, "s"):
        return leaf.q.astype(jnp.float32) * leaf.s.astype(jnp.float32)
    return leaf.astype(jnp.float32)


def rms_norm(x, scale, eps):
    import jax.numpy as jnp
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale.astype(jnp.float32)


def rope(x, theta):
    """x [S, heads, D]; rotate-half rotary embedding at positions 0..S-1."""
    import jax.numpy as jnp
    s, _, d = x.shape
    half = d // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.cache
def _layer_fn(dims: tuple):
    import jax
    import jax.numpy as jnp
    nh, nkv, d, eps, theta, bias = dims

    def layer(x, layers, index):
        lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, index, 0, keepdims=False), layers)
        s = x.shape[0]
        h = rms_norm(x, lp["input_norm"], eps)
        q = h @ plain(lp["wq"])
        k = h @ plain(lp["wk"])
        v = h @ plain(lp["wv"])
        if bias:
            q = q + lp["bq"].astype(jnp.float32)
            k = k + lp["bk"].astype(jnp.float32)
            v = v + lp["bv"].astype(jnp.float32)
        q = rope(q.reshape(s, nh, d), theta)
        k = rope(k.reshape(s, nkv, d), theta)
        v = v.reshape(s, nkv, d)
        group = nh // nkv
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d)
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, nh * d)
        x = x + attn @ plain(lp["wo"])
        h2 = rms_norm(x, lp["post_attn_norm"], eps)
        gate = h2 @ plain(lp["w_gate"])
        up = h2 @ plain(lp["w_up"])
        return x + (jax.nn.silu(gate) * up) @ plain(lp["w_down"])

    return jax.jit(layer)


@functools.cache
def _head_fn(eps: float, tied: bool, chunks: int):
    import jax
    import jax.numpy as jnp

    def head(x, final_norm, table):
        h = rms_norm(x, final_norm, eps)
        vocab_axis = 0 if tied else 1
        width = table_shape(table)[vocab_axis] // chunks

        def logits_of(c):
            # One slice of the vocabulary at a time: the float32 copy of a
            # 152,064-wide head is 2 GB, and the chip also holds the server.
            part = jax.tree.map(lambda a: jax.lax.dynamic_slice_in_dim(
                a, c * width, width, vocab_axis)
                if a.shape[vocab_axis] != 1 else a, table)
            w = plain(part)
            return h @ (w.T if tied else w)

        parts = jax.lax.map(logits_of, jnp.arange(chunks))  # [C, S, width]
        logits = jnp.moveaxis(parts, 0, 1).reshape(x.shape[0], -1)
        return jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)

    return jax.jit(head)


def table_shape(table) -> tuple:
    return (table.q if hasattr(table, "q") else table).shape


def teacher_forced(params, spec, prompt: list[int], generated: list[int],
                   layer, skip_layer: int | None = None) -> list[float]:
    """Logprob of each generated token under the plain forward of
    ``prompt + generated[:-1]``: embedding, ``layer(x, layers, index)`` for
    each layer but ``skip_layer``, final norm and head. What every block
    kind's reference shares; the block itself is ``layer``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    tokens = np.asarray(list(prompt) + list(generated[:-1]), np.int32)
    n_prompt, n_gen = len(prompt), len(generated)
    with jax.default_matmul_precision("highest"):
        embed = params["embed"]
        rows = (embed.q[tokens] if hasattr(embed, "q") else embed[tokens])
        x = rows.astype(jnp.float32)
        if hasattr(embed, "s"):
            x = x * embed.s.astype(jnp.float32)[0]
        for index in range(spec.num_layers):
            if index != skip_layer:
                x = layer(x, params["layers"], jnp.int32(index))
        tied = bool(spec.tie_word_embeddings)
        table = params["embed"] if tied else params["lm_head"]
        # Only the positions that predict a generated token reach the head.
        vocab = table_shape(table)[0 if tied else 1]
        chunks = next(c for c in (8, 4, 2, 1) if vocab % c == 0)
        logp = _head_fn(float(spec.rms_norm_eps), tied, chunks)(
            x[n_prompt - 1:n_prompt - 1 + n_gen], params["final_norm"],
            table)
        picked = logp[jnp.arange(n_gen), jnp.asarray(generated, jnp.int32)]
    return [float(v) for v in np.asarray(picked, np.float64)]


def reference_logprobs(params, spec, prompt: list[int],
                       generated: list[int], skip_layer: int | None = None
                       ) -> list[float]:
    """``teacher_forced`` through the dense block above. ``skip_layer``
    leaves that layer out: the check uses it on itself, to show what the
    tolerance would catch."""
    if getattr(spec, "num_experts", 0):
        raise NotImplementedError(
            "the dense reference has no expert layer: the configuration "
            "names its own (\"reference\": benchmark/references/<name>.py)")
    return teacher_forced(params, spec, prompt, generated,
                          _layer_fn(_dims(spec)), skip_layer)


def diff_stats(a, b) -> dict:
    """How far two lists of logprobs are apart, token by token: root mean
    square, median, 90th percentile and largest absolute difference."""
    d = sorted(abs(x - y) for x, y in zip(a, b))
    if not d:
        return {"rms": 0.0, "median": 0.0, "p90": 0.0, "worst": 0.0}
    return {"rms": math.sqrt(sum(x * x for x in d) / len(d)),
            "median": d[len(d) // 2], "p90": d[(len(d) * 9) // 10],
            "worst": d[-1]}


def judge(served, full, allowed: dict = ALLOWED_NATS) -> dict:
    """The verdict on one run's logprobs: ``served`` by the system, ``full``
    by the plain forward, ``allowed`` the largest median, root mean square
    and worst difference that pass."""
    near = diff_stats(served, full)
    ok = (len(served) == len(full) > 0
          and all(x == x and x <= 0.0 for x in served)
          and all(near[k] <= allowed[k] for k in ALLOWED_NATS))
    return {"ok": ok, **{k + "_nats": v for k, v in near.items()},
            "allowed_nats": {k: allowed[k] for k in ALLOWED_NATS},
            "samples": len(served)}


def for_config(config: dict, root: str = manifest.ROOT) -> dict:
    """The reference that judges a configuration. Its file may name one,
    ``"reference": "<name>"`` -> ``benchmark/references/<name>.py`` with
    ``reference_logprobs`` of the signature above and, optionally, its own
    ``ALLOWED_NATS`` (the measurement in its docstring); without the key it
    is this module. A name without its file is a ManifestError."""
    module, where = manifest.config_module(
        config, "reference", sys.modules[__name__], ("reference_logprobs",),
        root)
    return {"module": where, "logprobs": module.reference_logprobs,
            "allowed": getattr(module, "ALLOWED_NATS", ALLOWED_NATS)}
