"""The transformer block is written once (engine/model.py
``transformer_block``): each of the seven forward programs is traced with
the block wrapped by a counter, and every layer's two norms are found
inside it. A program that kept a hand copy of the block would trace the
block zero times and still normalise twice a layer.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from dynamo_tpu.engine import model
from dynamo_tpu.engine.backends import XLA
from dynamo_tpu.engine.config import ModelSpec
from dynamo_tpu.engine.runner import _prefill_with_history

SPEC = ModelSpec(name="one-block", vocab_size=64, hidden_size=32,
                 intermediate_size=48, num_layers=4, num_heads=4,
                 num_kv_heads=2, qkv_bias=True)
B, S, PAGE, PAGES, MAXP, W = 2, 8, 4, 16, 4, 4


def _count(jaxpr, name: str) -> int:
    """Equations of primitive ``name`` in a jaxpr and all it encloses."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count(sub, name)
    return n


def _programs():
    i32 = jnp.int32
    params = model.init_params(SPEC, jax.random.key(0))
    L, nkv, d = SPEC.num_layers, SPEC.num_kv_heads, SPEC.head_dim
    kv = jnp.zeros((L, nkv, PAGES, PAGE, d), jnp.bfloat16)
    buf = jnp.zeros((L, nkv, B, W, d), jnp.bfloat16)
    tok = jnp.ones((B, S), i32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=i32), (B, S))
    lens = jnp.full((B,), S, i32)
    chunk = jnp.arange(B * S // PAGE, dtype=i32).reshape(B, S // PAGE)
    table = jnp.arange(B * MAXP, dtype=i32).reshape(B, MAXP)
    return {
        "whole prompt": lambda: model.prefill_forward(
            params, SPEC, kv, kv, tok, pos, chunk, lens),
        "with history": lambda: _prefill_with_history(
            params, SPEC, kv, kv, tok, pos + S, chunk, lens, table, lens,
            XLA),
        "decode step": lambda: model.decode_forward(
            params, SPEC, kv, kv, tok[:, 0], lens, table, lens + 1),
        "decode window": lambda: model.decode_window_step(
            params, SPEC, kv, kv, buf, buf, i32(1), tok[:, 0], lens + 1,
            table, lens),
        "verify step": lambda: model.decode_window_multi_step(
            params, SPEC, kv, kv, buf, buf, jnp.ones((B,), i32), tok[:, :3],
            pos[:, :3] + S, table, lens),
        "embeddings": lambda: model.embed_forward(params, SPEC, tok, lens),
        "pipelined stage": lambda: model.prefill_forward_pipelined(
            params, SPEC, kv, kv, tok, pos, chunk, lens, n_stages=2),
    }


@pytest.mark.parametrize("program", [
    "whole prompt", "with history", "decode step", "decode window",
    "verify step", "embeddings", "pipelined stage"])
def test_every_layer_of_every_forward_program_goes_through_the_block(
        program, monkeypatch):
    calls = []
    real = model.transformer_block

    def counted(x, lp, *args, **kw):
        calls.append(lp["input_norm"].shape)
        return real(x, lp, *args, **kw)

    monkeypatch.setattr(model, "transformer_block", counted)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2, 1, 1),
                ("dp", "pp", "sp", "tp"))
    with mesh:
        jaxpr = jax.make_jaxpr(_programs()[program])().jaxpr
    # One trace of the block, inside the one scan over the layers (each
    # stage's own L/S under the pipelined prefill's vmap), on a layer's
    # slice of the stacked parameters.
    assert calls == [(SPEC.hidden_size,)]
    assert _count(jaxpr, "scan") == (2 if program == "pipelined stage"
                                     else 1)
    # rms_norm is the program's only rsqrt: the block's two and the final
    # norm. A layer normalised anywhere else is a second copy of the block.
    assert _count(jaxpr, "rsqrt") == 3
