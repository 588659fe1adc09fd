#!/usr/bin/env python3
"""How benchmark/tests/data/scoped_tpu.xplane.pb and scoped_tpu.scopes.json
were recorded (PR 25, one v5e): a few executions of a small jitted
``run_window`` whose regions carry the program's scopes
(dynamo_tpu/engine/perf.py SCOPES), under the profiler, while a second
thread plays the engine loop's phases (runtime/tracing.py PhaseClock) with
the device idle between executions. The map beside the trace is what the
compile registry gives for that executable (perf.scopes_of_hlo of its HLO
text). lib/scopes.py and lib/host_phases.py are tested on both. Run on a
machine with a TPU, from the root of the checkout:

    python3 benchmark/tests/data/record_scoped_trace.py <out-dir>
"""
import glob
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def main(out_dir: str) -> None:
    from dynamo_tpu.engine import perf
    from dynamo_tpu.runtime.tracing import PhaseClock
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")

    def run_window(pool, table, w, x):
        def step(x, _):
            with perf.scope("attn.kv_gather"):
                k = pool[table]                       # [B, P, D]
            with perf.scope("attn.core"):
                s = jnp.einsum("bd,bpd->bp", x, k)
                x = jnp.einsum("bp,bpd->bd", jax.nn.softmax(s, -1), k)
            with perf.scope("mlp"):
                x = jnp.tanh(x @ w)
            return x, None
        x, _ = jax.lax.scan(step, x, None, length=4)
        with perf.scope("sample"):
            tok = jnp.argmax(x, -1)
        with perf.scope("kv.commit"):
            pool = pool.at[table[:, 0]].set(x)
        return tok, pool

    fn = jax.jit(run_window, donate_argnums=(0,))
    key = jax.random.key(0)
    pool = jax.random.normal(key, (4096, 512), jnp.bfloat16)
    table = jnp.arange(32 * 64, dtype=jnp.int32).reshape(32, 64) % 4096
    w = jax.random.normal(key, (512, 512), jnp.bfloat16)
    x = jnp.ones((32, 512), jnp.bfloat16)
    text = fn.lower(pool, table, w, x).compile().as_text()
    scopes = perf.scopes_of_hlo(text)
    tok, pool = fn(pool, table, w, x)
    tok.block_until_ready()

    tmp = os.path.join(out_dir, "trace_tmp")
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    mono = time.monotonic_ns()
    with jax.profiler.TraceAnnotation(f"bench.mark mono_ns={mono}"):
        time.sleep(0.001)
    clock = PhaseClock()
    for i in range(6):
        with clock.phase("engine.admit"):
            time.sleep(0.002)             # the device is idle: the host's
        with clock.phase("engine.dispatch_window"):
            tok, pool = fn(pool, table, w, x)
        with clock.phase("engine.process_window"):
            with clock.phase("engine.readback_wait"):
                tok.block_until_ready()
            time.sleep(0.001)
        if i % 2:
            with clock.phase("engine.idle"):
                time.sleep(0.002)         # idle, and nobody's fault
    jax.profiler.stop_trace()
    src = sorted(glob.glob(os.path.join(
        tmp, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    dst = os.path.join(out_dir, "scoped_tpu.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    with open(os.path.join(out_dir, "scoped_tpu.scopes.json"), "w",
              encoding="utf-8") as fh:
        json.dump({"program": "run_window", "ops_by_scope": scopes}, fh,
                  indent=0, sort_keys=True)
    print(dst, os.path.getsize(dst), len(scopes))


if __name__ == "__main__":
    main(sys.argv[1])
