"""Decompose the decode-step time on the real chip.

One-call timing measures the dispatch, not the op, so every measurement
here chains ITERS iterations inside ONE jitted lax.scan and divides — the same
amortization the serving engine's decode windows use. Run on TPU:
``python -m scripts.profile_decode``.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.config import PRESETS, device_peaks
from dynamo_tpu.engine.model import (
    decode_forward, init_params, paged_decode_attention_xla)
from dynamo_tpu.engine.sampler import sample_tokens

ITERS = 64


def timed(label, fn, *args, reps=5):
    fn(*args)
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.monotonic()
        jax.block_until_ready(fn(*args))
        best = min(best, (time.monotonic() - t0) / ITERS * 1e3)
    print(f"{label}: {best * 1e3:.0f} us/iter")
    return best


def main():
    peaks = device_peaks(jax.devices()[0])
    if peaks is None:
        raise SystemExit("this script times a TPU; jax found the CPU backend")
    spec = PRESETS["qwen2.5-0.5b"]
    batch, page = 32, 16
    params = init_params(spec, jax.random.key(0))

    rng = jax.random.key(1)
    temp = jnp.zeros((batch,), jnp.float32)
    top_k = jnp.zeros((batch,), jnp.int32)
    top_p = jnp.ones((batch,), jnp.float32)

    # Sampler: scan-chained.
    logits0 = jnp.zeros((batch, spec.vocab_size), jnp.float32)

    @jax.jit
    def samp_chain(lg, r):
        def body(carry, _):
            lg, r = carry
            r, sub = jax.random.split(r)
            t = sample_tokens(lg, temp, top_k, top_p, sub)
            # fold the token back in so the scan can't be elided
            lg2 = lg + t[:, None] * 1e-9
            return (lg2, r), ()
        (lg, r), _ = jax.lax.scan(body, (lg, r), None, length=ITERS)
        return lg
    timed("sampler", samp_chain, logits0, rng)

    for maxp in (8, 16, 32, 64):
        num_pages = batch * maxp + 16
        kv_shape = (spec.num_layers, spec.num_kv_heads, num_pages, page,
                    spec.head_dim)
        k = jnp.zeros(kv_shape, jnp.bfloat16)
        v = jnp.zeros(kv_shape, jnp.bfloat16)
        pt = np.zeros((batch, maxp), np.int32)
        for b in range(batch):
            pt[b] = np.arange(1 + b * maxp, 1 + (b + 1) * maxp)
        page_table = jnp.asarray(pt)
        seq_lens = jnp.full((batch,), maxp * page - 8, jnp.int32)
        positions = seq_lens - 1
        tokens = jnp.zeros((batch,), jnp.int32)

        # Full forward, scan-chained (token feedback like the real window).
        def fwd_chain_of(impl):
            @jax.jit
            def fwd_chain(params, k, v):
                def body(carry, _):
                    k, v, tok = carry
                    lg, k, v = decode_forward(
                        params, spec, k, v, tok, positions, page_table,
                        seq_lens, attention_impl=impl)
                    tok = jnp.argmax(lg, -1).astype(jnp.int32)
                    return (k, v, tok), ()
                (k, v, tok), _ = jax.lax.scan(
                    body, (k, v, tokens), None, length=ITERS)
                return tok
            return fwd_chain

        t_x = timed(f"forward+argmax maxp={maxp} xla",
                    fwd_chain_of(paged_decode_attention_xla), params, k, v)
        try:
            from dynamo_tpu.engine.attention import (
                paged_decode_attention_pallas)
            t_p = timed(f"forward+argmax maxp={maxp} pallas",
                        fwd_chain_of(paged_decode_attention_pallas),
                        params, k, v)
            print(f"  -> pallas/xla = {t_p / t_x:.2f}")
        except Exception as e:  # noqa: BLE001
            print("pallas failed:", type(e).__name__, str(e)[:300])

    # Weight-read roofline context (bandwidth from config.DEVICE_PEAKS).
    pb = spec.num_params() * 2
    print(f"params {pb / 1e9:.2f} GB -> weight-read floor = "
          f"{spec.weight_read_step_ms(peaks.hbm_gbps) * 1e3:.0f} us/step")


if __name__ == "__main__":
    main()
