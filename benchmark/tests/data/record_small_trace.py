#!/usr/bin/env python3
"""How benchmark/tests/data/small_tpu.xplane.pb was recorded (PR 24, one
v5e): a few executions of two small jitted programs under the profiler,
with the benchmark's clock mark, so that trace_reduce.py is tested on the
planes, lines and names a real TPU trace has. Run on a machine with a TPU:

    python3 benchmark/tests/data/record_small_trace.py <out-dir>
"""
import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")

    @jax.jit
    def run_window(x):
        for _ in range(4):
            x = jnp.tanh(x @ x)
        return x

    @jax.jit
    def step(x):
        return (x * 2.0).sum()

    x = jnp.ones((512, 512), jnp.bfloat16)
    run_window(x).block_until_ready()
    step(x).block_until_ready()
    tmp = os.path.join(out_dir, "trace_tmp")
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    mono = time.monotonic_ns()
    with jax.profiler.TraceAnnotation(f"bench.mark mono_ns={mono}"):
        time.sleep(0.001)
    for i in range(6):
        run_window(x).block_until_ready()
        if i % 2:
            step(x).block_until_ready()
        time.sleep(0.002)
    jax.profiler.stop_trace()
    src = sorted(glob.glob(os.path.join(
        tmp, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    dst = os.path.join(out_dir, "small_tpu.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    print(dst, os.path.getsize(dst))


if __name__ == "__main__":
    main(sys.argv[1])
