"""Test configuration.

Distributed/sharding tests run on a virtual 8-device CPU mesh (no TPUs needed),
mirroring the reference's strategy of testing the distributed stack with local
processes + simulators (SURVEY.md §4). Set env BEFORE jax import.
"""

import os

# Hard-set (not setdefault): the tests are CPU tests wherever they run. On a
# machine with a chip the environment selects the TPU, and a test process that
# touched it would own it; spawned workers inherit this value.
os.environ["JAX_PLATFORMS"] = "cpu"
# The engine keeps a persistent compile cache (engine/perf.py
# configure_compile_cache). Tests get a fixed directory of their own, so they
# never write into the serving cache (<repo>/.jax_cache) by accident; spawned
# workers inherit it. Many tests build the same tiny programs in fresh jit
# closures: even a cold cache makes every build after the first a load.
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), ".jax_cache")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("DTPU_LOG", "warning")

import jax

jax.config.update("jax_platforms", "cpu")

import asyncio
import functools

import pytest


def async_test(fn=None, *, timeout: float = 120):
    """Run an async test function to completion on a fresh event loop
    (pytest-asyncio is not available in this environment). Use
    ``@async_test`` for the default budget or ``@async_test(timeout=N)``
    for e2e tests whose bring-up scales with machine load (multi-process
    spawns compiling JAX programs on a contended box)."""

    def deco(f):
        @functools.wraps(f)
        def wrapper(*args, **kwargs):
            return asyncio.run(
                asyncio.wait_for(f(*args, **kwargs), timeout=timeout))

        return wrapper

    return deco if fn is None else deco(fn)


@pytest.fixture
def anyio_backend():
    return "asyncio"


#: One tiny engine behind the unified launcher, on a port of its own.
TINY_LAUNCH = ["in=http", "out=tpu", "--model", "tiny-test", "--http-host",
               "127.0.0.1", "--http-port", "0", "--num-pages", "64",
               "--max-num-seqs", "4"]


async def launched(argv, inside):
    """``launch.run`` to ready, ``await inside(runtime, service, engine)``,
    shutdown; what ``inside`` returned."""
    from dynamo_tpu import launch
    args = launch.parse_args(argv)
    ready = asyncio.get_running_loop().create_future()
    task = asyncio.create_task(
        launch.run(args, ready=lambda *a: ready.set_result(a)))
    await asyncio.wait({task, ready}, return_when=asyncio.FIRST_COMPLETED)
    if not ready.done():
        task.result()   # raises what the start raised
        raise AssertionError("launch.run returned before it was ready")
    runtime, service, engine = ready.result()
    try:
        return await inside(runtime, service, engine)
    finally:
        runtime.shutdown()
        await task
