"""Seeded model parameters made on the device, shard by shard.

The launcher's own random init runs threefry on the host and quantizes in
numpy: minutes for 7B parameters. Here every parameter is one jitted call
whose output carries the sharding the runner will ask for (``param_specs``
on the runner's mesh), so nothing is built on the host, nothing lands whole
on chip 0 at tp > 1, and a stacked leaf is made a layer at a time
(``lax.map``) so the RNG's temporaries stay one layer large.

Values follow the program's init (engine/model.py ``init_params``: normal /
sqrt(fan_in), norm scales one) and, for int8, its quantizer (engine/quant.py:
symmetric per-output-channel, embedding per hidden channel). The tree is
checked against ``param_specs`` so a change of the program's tree fails here
and not in the runner.
"""

from __future__ import annotations

import numpy as np

#: The ONE draw of the weights every cell of BENCHMARK.json is served with
#: (run.py hands it to server.Seams) and of the timed prompts' words
#: (run.py ``timed_words``); ``--seed`` draws the check's prompts.
#: Since PR 56 a decode step reads only the experts its live rows chose, so
#: its time follows the routing, and the routing is the draw's: constant
#: inside a run and another between two draws, which no longer window
#: averages out (five routed cells spread 1.3 to 5.4 % over six seeds, ledger,
#: PR 57). With the weights held and the words still the seed's, two cells
#: spread 0.59 % over three seeds where one seed twice read 0.01 % (my chip
#: run, PR 58, call 1): the words choose among the draw's experts, so they
#: are the replay's too. The sibling of generators/closed_loop.py ``ORDER``:
#: a cell replays one trace, words and all, over one draw of the weights.
#: The builders' tools that read a tolerance over many seeds (long_prompt.py,
#: selection_check.py, block_selection_check.py, draft_check.py) give
#: ``Seams`` their own.
CELL_WEIGHTS_SEED = 58


def runner_mesh(config, devices=None):
    """The mesh ModelRunner builds for this EngineConfig."""
    import jax
    from jax.sharding import Mesh
    devices = devices if devices is not None else jax.devices()
    total = config.dp * config.pp * config.sp * config.tp
    if len(devices) < total:
        raise ValueError(f"need {total} devices, have {len(devices)}")
    grid = np.array(devices[:total]).reshape(
        config.dp, config.pp, config.sp, config.tp)
    return Mesh(grid, ("dp", "pp", "sp", "tp"))


def make_params(spec, mesh, seed: int):
    """The parameter tree of ``spec`` (QTensor leaves when spec.quant is
    "int8"), on the devices of ``mesh`` under the runner's shardings."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dynamo_tpu.engine.model import param_shapes, param_specs
    from dynamo_tpu.engine.quant import QUANT_LAYER_KEYS, QTensor

    shapes = param_shapes(spec)
    pspecs = param_specs(spec)
    int8 = spec.quant == "int8"

    def sharding_of(pspec):
        return jax.tree.map(lambda p: NamedSharding(mesh, p), pspec,
                            is_leaf=lambda x: isinstance(x, P))

    def dense(key, shape, fan_in):
        # init_params: normal over sqrt(fan_in), fan_in the size of the
        # axis before the last of the WHOLE leaf (for a stacked bias that is
        # the layer count).
        scale = (1.0 / jnp.sqrt(fan_in)).astype(jnp.bfloat16)
        return jax.random.normal(key, shape, jnp.bfloat16) * scale

    def quantized(w, axis):
        wf = w.astype(jnp.float32)
        amax = jnp.max(jnp.abs(wf), axis=axis, keepdims=True)
        s = jnp.where(amax == 0, 1.0, amax / 127.0).astype(jnp.float32)
        q = jnp.clip(jnp.rint(wf / s), -127, 127).astype(jnp.int8)
        return QTensor(q=q, s=s)

    def build(name, shape, stacked, quant_axis):
        def one(key, shp):
            w = dense(key, shp, shape[-2])
            return w if quant_axis is None else quantized(w, quant_axis)

        def fn(key):
            if name.endswith("_norm"):
                return jnp.ones(shape, jnp.bfloat16)
            if stacked:
                keys = jax.random.split(key, shape[0])
                return jax.lax.map(lambda k: one(k, shape[1:]), keys)
            return one(key, shape)
        return fn

    root = jax.random.key(seed & 0x7FFFFFFF)
    names = sorted(list(shapes["layers"]) + [k for k in shapes
                                             if k != "layers"])
    keys = dict(zip(names, jax.random.split(root, len(names))))

    def make(name, shape, pspec, stacked):
        quant_axis = None
        if int8 and name == "embed":
            quant_axis = 0
        elif int8 and (name == "lm_head" or name in QUANT_LAYER_KEYS):
            quant_axis = -2
        fn = jax.jit(build(name, shape, stacked, quant_axis),
                     out_shardings=sharding_of(pspec))
        return fn(keys[name])

    params = {name: make(name, shape, pspecs[name], False)
              for name, shape in shapes.items() if name != "layers"}
    params["layers"] = {
        name: make(name, shape, pspecs["layers"][name], True)
        for name, shape in shapes["layers"].items()}
    is_p = lambda x: isinstance(x, P)  # noqa: E731
    if (jax.tree.structure(params)
            != jax.tree.structure(pspecs, is_leaf=is_p)):
        raise RuntimeError("device-made parameters do not match the "
                           "program's param_specs tree")
    jax.block_until_ready(params)
    return params


def param_bytes_on(params, device) -> int:
    """Bytes of the parameter shards resident on ``device``."""
    import jax
    return sum(s.data.nbytes for leaf in jax.tree.leaves(params)
               for s in leaf.addressable_shards if s.device == device)
