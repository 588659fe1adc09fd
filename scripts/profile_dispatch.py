"""Measure per-call dispatch/transfer overhead on the attached TPU."""

import time

import jax
import jax.numpy as jnp
import numpy as np


def main():
    x = jnp.zeros((8, 128), jnp.float32)
    f = jax.jit(lambda x: x + 1)
    jax.block_until_ready(f(x))
    t0 = time.monotonic()
    n = 50
    for _ in range(n):
        x = f(x)
    jax.block_until_ready(x)
    print("chained jit call (device-resident):",
          round((time.monotonic() - t0) / n * 1e3, 2), "ms")

    t0 = time.monotonic()
    for _ in range(n):
        y = jax.block_until_ready(f(x))
    print("jit call + block each:",
          round((time.monotonic() - t0) / n * 1e3, 2), "ms")

    host = np.zeros((32,), np.int32)
    t0 = time.monotonic()
    for _ in range(n):
        d = jnp.asarray(host)
    jax.block_until_ready(d)
    print("h2d small array:", round((time.monotonic() - t0) / n * 1e3, 2),
          "ms")

    d = jnp.zeros((32,), jnp.int32)
    t0 = time.monotonic()
    for _ in range(n):
        _ = np.asarray(jax.device_get(d))
    print("d2h small array:", round((time.monotonic() - t0) / n * 1e3, 2),
          "ms")

    # Pallas at D=128?
    try:
        from dynamo_tpu.engine.attention import paged_decode_attention_pallas
        b, nkv, qpk, dd, pages, page, maxp = 4, 8, 4, 128, 64, 16, 8
        q = jnp.zeros((b, nkv * qpk, dd), jnp.bfloat16)
        kc = jnp.zeros((2, nkv, pages, page, dd), jnp.bfloat16)
        ks = jnp.zeros((b, nkv, dd), jnp.bfloat16)
        pt = jnp.zeros((b, maxp), jnp.int32)
        sl = jnp.full((b,), 20, jnp.int32)
        out = paged_decode_attention_pallas(
            q, kc, kc, jnp.asarray(0, jnp.int32), pt, sl, ks, ks, qpk)
        out = np.asarray(out)
        print("pallas D=128 OK", out.shape)
    except Exception as e:  # noqa: BLE001
        print("pallas D=128 failed:", type(e).__name__, str(e)[:500])


if __name__ == "__main__":
    main()
