"""Decompose the 8B int8 decode step on the real chip (round-5 ask:
"profile the non-weight-read 45%").

Scan-amortized in-graph timings (per-call dispatch would otherwise
dominate; same technique as profile_decode.py) at the
8B serving shapes: bs, page-table width, xla vs pallas attention, and
the sampler chain. The residual between the ENGINE's measured ITL
(bench.py) and the in-graph step is host dispatch + readback overlap.

Run: BENCH_MODEL=llama-3-8b PROF_BS=18 python scripts/profile_8b_step.py
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

MODEL = os.environ.get("BENCH_MODEL", "llama-3-8b")
BS = int(os.environ.get("PROF_BS", "18"))
MAXP = int(os.environ.get("PROF_MAXP", "16"))   # pages/slot in the table
ITERS = int(os.environ.get("PROF_ITERS", "32"))
QUANT = os.environ.get("BENCH_QUANT", "int8")


def timed(fn, *args, reps=3):
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.monotonic()
        jax.block_until_ready(fn(*args))
        best = min(best, (time.monotonic() - t0) / ITERS * 1e3)
    return best  # ms/iter


def main() -> None:
    from dynamo_tpu.engine.config import PRESETS, device_peaks
    from dynamo_tpu.engine.model import (decode_forward, init_params,
                                         paged_decode_attention_xla)
    from dynamo_tpu.engine.sampler import sample_tokens

    peaks = device_peaks(jax.devices()[0])
    if peaks is None:
        raise SystemExit("this script times a TPU; jax found the CPU backend")
    spec = PRESETS[MODEL]
    if QUANT and QUANT != "none":
        spec = dataclasses.replace(spec, quant=QUANT)
    floor_ms = spec.weight_read_step_ms(peaks.hbm_gbps)
    page = 16
    num_pages = BS * MAXP + 16
    # Timing-only weights: build the (possibly quantized) param tree
    # DIRECTLY on device from its eval_shape — host-RNG init of 8B takes
    # ~20 min on this 1-vCPU box and the values are irrelevant here.
    def build(key):
        p = init_params(spec, key)
        if spec.quant == "int8":
            # Traceable twin of quant.quantize_params (that one is
            # numpy/host-side; eval_shape needs jnp).
            from dynamo_tpu.engine.quant import QUANT_LAYER_KEYS, QTensor

            def qw(w, emb=False):
                wf = w.astype(jnp.float32)
                amax = jnp.max(jnp.abs(wf), axis=0 if emb else -2,
                               keepdims=True)
                s = jnp.where(amax == 0, 1.0, amax / 127.0)
                return QTensor(
                    q=jnp.clip(jnp.rint(wf / s), -127, 127)
                    .astype(jnp.int8), s=s)

            layers = dict(p["layers"])
            for k2 in QUANT_LAYER_KEYS:
                if k2 in layers:
                    layers[k2] = qw(layers[k2])
            p = dict(p)
            p["layers"] = layers
            p["embed"] = qw(p["embed"], emb=True)
            if "lm_head" in p:
                p["lm_head"] = qw(p["lm_head"])
        return p

    shapes = jax.eval_shape(build, jax.random.key(0))
    flat, treedef = jax.tree.flatten(shapes)

    @jax.jit
    def make_params():
        out = []
        for i, sds in enumerate(flat):
            key = jax.random.fold_in(jax.random.key(7), i)
            if np.issubdtype(sds.dtype, np.integer):
                out.append(jax.random.randint(
                    key, sds.shape, -127, 127, dtype=jnp.int32)
                    .astype(sds.dtype))
            else:
                out.append((jax.random.normal(key, sds.shape,
                                              jnp.float32) * 0.02 + 0.01)
                           .astype(sds.dtype))
        return tuple(out)

    params = jax.tree.unflatten(treedef, list(make_params()))
    kv_shape = (spec.num_layers, spec.num_kv_heads, num_pages, page,
                spec.head_dim)
    k_cache = jnp.zeros(kv_shape, jnp.bfloat16)
    v_cache = jnp.zeros(kv_shape, jnp.bfloat16)
    pt = np.zeros((BS, MAXP), np.int32)
    for b in range(BS):
        pt[b] = np.arange(1, MAXP + 1)  # disjoint-ish enough for timing
    pt = jnp.asarray(pt)
    seq_lens = jnp.full((BS,), MAXP * page - 4, jnp.int32)
    positions = seq_lens
    tokens = jnp.ones((BS,), jnp.int32)

    def fwd_chain_of(impl):
        @jax.jit
        def chain(params, k, v, tok):
            def body(carry, _):
                t, k, v = carry
                logits, k, v = decode_forward(
                    params, spec, k, v, t, positions, pt, seq_lens,
                    attention_impl=impl)
                return (jnp.argmax(logits, -1).astype(jnp.int32), k, v), ()
            (t, k, v), _ = jax.lax.scan(body, (tok, k, v), None,
                                        length=ITERS)
            return t, k, v
        return chain

    only = os.environ.get("PROF_ONLY", "xla")  # xla|pallas|wide|sampler
    results = {"metric": f"decode_step_breakdown_{spec.name}_bs{BS}",
               "leg": only,
               "weight_read_floor_ms": round(floor_ms, 3)}
    if only == "xla":
        ms = timed(fwd_chain_of(paged_decode_attention_xla), params,
                   k_cache, v_cache, tokens)
        results["fwd_xla_ms"] = round(ms, 3)
        results["non_weight_in_graph_ms"] = round(
            ms - floor_ms, 3)
        results["mfu_in_graph"] = round(floor_ms / ms, 3)
    elif only == "pallas":
        from dynamo_tpu.engine.attention import paged_decode_attention_pallas
        ms = timed(fwd_chain_of(paged_decode_attention_pallas), params,
                   k_cache, v_cache, tokens)
        results["fwd_pallas_ms"] = round(ms, 3)
    elif only == "wide":
        # Page-table width sensitivity: the layer-folded gather reads
        # the WHOLE bucketed table per row; widening isolates the
        # gather leg: gather_ms ~= (wide4x - base) / 3.
        wide = MAXP * 4
        ptw = jnp.asarray(
            np.tile(np.arange(1, wide + 1, dtype=np.int32),
                    (BS, 1)) % (num_pages - 1) + 1)

        @jax.jit
        def chain_wide(params, k, v, tok):
            def body(carry, _):
                t, k, v = carry
                logits, k, v = decode_forward(
                    params, spec, k, v, t, positions, ptw, seq_lens,
                    attention_impl=paged_decode_attention_xla)
                return (jnp.argmax(logits, -1).astype(jnp.int32), k, v), ()
            (t, k, v), _ = jax.lax.scan(body, (tok, k, v), None,
                                        length=ITERS)
            return t, k, v

        results["fwd_xla_wide4x_ms"] = round(
            timed(chain_wide, params, k_cache, v_cache, tokens), 3)
    elif only == "sampler":
        lg = jax.random.normal(jax.random.key(1), (BS, spec.vocab_size),
                               jnp.float32)

        @jax.jit
        def samp_chain(lg, r):
            def body(carry, _):
                r, = carry
                r, sub = jax.random.split(r)
                s = sample_tokens(lg, jnp.full((BS,), 0.7),
                                  jnp.full((BS,), 50, jnp.int32),
                                  jnp.full((BS,), 0.9), sub)
                return (r,), s
            (r,), s = jax.lax.scan(body, (r,), None, length=ITERS)
            return s

        results["sampler_ms"] = round(timed(samp_chain, lg,
                                            jax.random.key(2)), 3)
    print(json.dumps(results))


if __name__ == "__main__":
    main()
    sys.stdout.flush()
    os._exit(0)
