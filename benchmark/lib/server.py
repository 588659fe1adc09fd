"""The system under test, started inside this process through launch.run.

The server is exactly what ``python -m dynamo_tpu.launch in=http out=tpu
--model <name> ...`` starts (overload limiter, observability and all). Two
things are put in from outside, because the launcher has no option for
them yet; PERF.md lists both as what a launcher option should replace:

  seam 1  the configuration file's ModelSpec is registered in
          engine.config.PRESETS under the configuration's name before the
          launcher parses its arguments (hub.resolve_model takes only
          presets or real checkpoints);
  seam 2  ``dynamo_tpu.engine.engine.TPUEngine``, which launch._build_engine
          imports at call time, is replaced by a subclass that (a) sizes
          the KV pool with the runner's own rule on the still-empty device,
          (b) fills in ``params`` made on the device (lib/weights.py) when
          the launcher passes None, and (c) after the engine's own warm-up
          runs every prefill and window shape the cell's traffic can draw,
          with inert rows, as the engine's own ladder does.
"""

from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import json
import os
import sys
import time

import numpy as np

from benchmark.lib import manifest, weights


class BenchError(Exception):
    pass


def model_spec(name: str, cfg: dict, quant: str | None):
    """ModelSpec of a configuration, read by the program's own reader
    (engine/config.py ``ModelSpec.from_hf_config``) from a copy of the
    dictionary as it is run, written under .bench_run/: a key a later
    architecture needs is then read by the program, not mapped here."""
    from dynamo_tpu.engine.config import ModelSpec
    os.makedirs(manifest.RUN_DIR, exist_ok=True)
    path = os.path.join(manifest.RUN_DIR, f"config-{name}.json")
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(cfg, fh)
    return dataclasses.replace(ModelSpec.from_hf_config(path), name=name,
                               quant=quant)


#: Rows of one batched prefill call. The engine has no setting for it:
#: TPUEngine._admit cuts the staged prompts into groups of 8
#: (``chunk, group = group[:8], group[8:]``); PERF.md lists it among what
#: the program should expose.
PREFILL_GROUP_ROWS = 8


@dataclasses.dataclass
class WarmShapes:
    """What the cell's traffic can make the engine compile."""
    max_prompt: int      # longest prompt, template included
    max_context: int     # longest prompt + output
    max_batch: int = PREFILL_GROUP_ROWS  # rows of one prefill call


class Seams:
    """Installs both seams; ``restore()`` undoes them."""

    def __init__(self, name: str, spec, seed: int, shapes: WarmShapes):
        self.name, self.spec, self.seed = name, spec, seed
        self.shapes = shapes
        self.timings: dict = {}
        self._undo = []

    def install(self) -> None:
        from dynamo_tpu.engine import config as config_mod
        from dynamo_tpu.engine import engine as engine_mod
        had = config_mod.PRESETS.get(self.name)
        config_mod.PRESETS[self.name] = self.spec
        self._undo.append(lambda: (
            config_mod.PRESETS.pop(self.name, None) if had is None
            else config_mod.PRESETS.__setitem__(self.name, had)))
        base = engine_mod.TPUEngine
        seams = self

        class BenchEngine(base):
            def __init__(self, config, params=None, **kw):
                if params is None:
                    config, params = seams._prepare(config)
                super().__init__(config, params=params, **kw)

            def _warmup_window_programs(self):
                t0 = time.monotonic()
                super()._warmup_window_programs()
                seams.timings["engine_warmup_s"] = time.monotonic() - t0
                t0 = time.monotonic()
                seams.timings["warmed"] = warm_traffic_shapes(
                    self, seams.shapes)
                seams.timings["traffic_warmup_s"] = time.monotonic() - t0

        engine_mod.TPUEngine = BenchEngine
        self._undo.append(lambda: setattr(engine_mod, "TPUEngine", base))

    def restore(self) -> None:
        while self._undo:
            self._undo.pop()()

    def _prepare(self, config):
        """Pool size by the runner's own rule, then device-made weights."""
        from dynamo_tpu.engine import perf
        from dynamo_tpu.engine.runner import ModelRunner
        perf.configure_compile_cache()  # before this module's first compile
        spec = config.model
        if config.tp > spec.num_kv_heads:
            raise BenchError("tp > num_kv_heads needs KV-head replication "
                             "of host weights; not supported here")
        mesh = weights.runner_mesh(config)
        if config.num_pages is None:
            # ModelRunner sizes the pool from free memory BEFORE it loads
            # parameters. Ours are on the device by then, so the same rule
            # is applied now, on the empty device, and the result pinned.
            probe = object.__new__(ModelRunner)
            probe.config, probe.spec = config, spec
            probe.quant_kv = config.resolve_quant_kv()
            probe._sized_pages(mesh.devices.flat[0])
            config = dataclasses.replace(config, num_pages=probe.num_pages)
        t0 = time.monotonic()
        params = weights.make_params(spec, mesh, self.seed)
        self.timings["weights_s"] = time.monotonic() - t0
        self.timings["param_bytes_chip0"] = weights.param_bytes_on(
            params, mesh.devices.flat[0])
        self.timings["devices"] = [str(d) for d in mesh.devices.flat]
        return config, params


def warm_traffic_shapes(engine, shapes: WarmShapes) -> dict:
    """ENGINE THREAD, before the engine reports ready. Runs each program
    the traffic can draw once, on inert rows (zero tokens, every write to
    the reserved scratch page 0), exactly as TPUEngine's own
    _warmup_prefill_ladder does for batch size 1."""
    from dynamo_tpu.engine.runner import PK_PREFIX, PrefillSeq
    cfg, runner = engine.config, engine.runner
    buckets = [b for b in cfg.prefill_buckets
               if b <= cfg.bucket_for(shapes.max_prompt)]
    batches = [b for b in (1, 2, 4, 8, 16, 32) if b <= shapes.max_batch]
    failed = []

    def seq(bucket):
        return PrefillSeq(tokens=np.zeros(bucket, np.int32), start_pos=0,
                          chunk_pages=np.zeros(1, np.int32), hist_pages=None,
                          sampling=(0.0, 0, 1.0))

    for bucket in buckets:
        for rows in batches:
            try:
                runner.prefill_batch([seq(bucket)] * rows, fetch=False)
            except Exception as exc:  # noqa: BLE001 — reported below
                failed.append([bucket, rows, f"{type(exc).__name__}: "
                               f"{str(exc)[:200]}"])
    # The small eager programs that place first tokens in their slots
    # depend on the number of rows, not on the batch bucket.
    for rows in range(1, batches[-1] + 1):
        handle = runner.prefill_batch([seq(buckets[0])] * rows,
                                      slots=list(range(rows)))
        np.asarray(handle["tokens"])
    page = cfg.page_size
    widths, need = [], 1
    top = runner.bucket_pages_for(-(-shapes.max_context // page) + 1)
    while True:
        width = runner.bucket_pages_for(need)
        widths.append(width)
        if width >= top:
            break
        need = width + 1
    for width in widths:
        packed = np.zeros((cfg.max_num_seqs, PK_PREFIX + width), np.int32)
        outs = runner.decode_window(packed, engine.decode_window)
        np.asarray(outs[0])
    return {"prefill_buckets": buckets, "prefill_batches": batches,
            "window_page_buckets": widths, "failed": failed}


class EngineTap:
    """Sits between the detokenizing Backend and the engine and records what
    the engine emits for each request, with the time of each emission on
    time.monotonic(). Everything else forwards to the engine."""

    def __init__(self, engine):
        self._engine = engine
        self.calls: list[dict] = []

    def __getattr__(self, name):
        return getattr(self._engine, name)

    async def generate(self, request, context):
        rec = {"prompt": list(request.token_ids), "tokens": [],
               "logprobs": [], "t_in": time.monotonic(), "t_out": []}
        self.calls.append(rec)
        async for out in self._engine.generate(request, context):
            ids = out.get("token_ids", [])
            if ids:
                rec["t_out"].append(time.monotonic())
            rec["tokens"].extend(ids)
            rec["logprobs"].extend(out.get("log_probs") or [])
            yield out


class Server:
    """The unified launcher's HTTP server, started inside this process."""

    def __init__(self, launch_argv: list[str]):
        self.launch_argv = launch_argv
        self.task = None
        self.runtime = self.service = self.engine = self.tap = None
        self.session = None
        self.startup_s = None

    async def __aenter__(self):
        from dynamo_tpu import launch
        largs = launch.parse_args(self.launch_argv)
        loop = asyncio.get_running_loop()
        ready = loop.create_future()
        t0 = time.monotonic()
        # launch.run prints its LAUNCH_READY line; stdout here is JSON only.
        with contextlib.redirect_stdout(sys.stderr):
            self.task = asyncio.create_task(launch.run(
                largs, ready=lambda *a: ready.set_result(a)))
            await asyncio.wait({self.task, ready},
                               return_when=asyncio.FIRST_COMPLETED)
        if not ready.done():
            self.task.result()  # raises what start-up raised
            raise BenchError("launcher returned before it was ready")
        self.runtime, self.service, self.engine = ready.result()
        self.startup_s = time.monotonic() - t0
        try:
            return await self._attach(largs)
        except BaseException:
            await self.__aexit__()
            raise

    async def _attach(self, largs):
        import aiohttp
        served = self.service.manager.models[largs.model]
        backend = served.preprocessor.inner
        if backend.inner is not self.engine:
            raise BenchError("pipeline is not preprocessor -> backend -> "
                             "engine")
        self.tap = backend.inner = EngineTap(self.engine)
        self.model = largs.model
        self.chat_template = served.preprocessor.card.chat_template
        self.base = f"http://127.0.0.1:{self.service.port}"
        self.session = aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=600))
        for _ in range(100):
            async with self.session.get(self.base + "/health") as resp:
                if resp.status == 200:
                    return self
            await asyncio.sleep(0.1)
        raise BenchError("/health never answered 200")

    async def __aexit__(self, *exc):
        if self.session is not None:
            await self.session.close()
        if self.runtime is not None:
            self.runtime.shutdown()  # launch.run stops service + engine
        if self.task is not None:
            await self.task
        if self.engine is not None:
            # The engine thread has stopped; wait for what it dispatched
            # last, then drop every device array before the interpreter
            # goes (a run ended in a segmentation fault otherwise).
            import gc

            import jax
            runner = self.engine.runner
            jax.block_until_ready((runner.k_cache, runner.v_cache,
                                   runner.tokens_dev))
            del runner
        self.runtime = self.service = self.engine = self.tap = None
        if self.task is not None:
            gc.collect()

    async def get_text(self, path: str) -> str:
        async with self.session.get(self.base + path) as resp:
            if resp.status != 200:
                raise BenchError(f"GET {path} -> {resp.status}")
            return await resp.text()

    async def chat(self, content: str, max_tokens: int, **extra) -> dict:
        """One non-streamed /v1/chat/completions call; the body plus
        ``_tap``, what the engine emitted for it."""
        body = {"model": self.model, "max_tokens": max_tokens,
                "messages": [{"role": "user", "content": content}],
                "temperature": 0.0, "ignore_eos": True, **extra}
        n_before = len(self.tap.calls)
        async with self.session.post(self.base + "/v1/chat/completions",
                                     json=body) as resp:
            text = await resp.text()
            if resp.status != 200:
                raise BenchError(f"chat -> {resp.status}: {text[:300]}")
        out = json.loads(text)
        out["_tap"] = self.tap.calls[n_before:]
        return out
