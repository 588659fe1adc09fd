"""Bytes and operations of one decode step of a looped stack (``ouro``), from
shapes alone, on one chip: lib/roofline.py's reckoning with the layers read
once a PASS and K and V kept a (pass, layer) pair.

The passes are sequential (pass t + 1 of the first layer needs pass t of the
last) and no layer stays on the chip between two of them, so one decode step
must at least
  * read every layer's matrices and its four norm vectors ``total_ut_steps``
    times, as stored (int8 values and their float32 scales, or bf16), the
    untied head and the final norm once, and one row of the embedding table
    a sequence;
  * read the K and V of every live token in all ``total_ut_steps x
    num_hidden_layers`` pool layers (a pass attends its own entries, so none
    is read twice and none can be skipped) and write one token's worth a
    sequence;
  * do 2 operations a weight VISITED a sequence, and 4 a live token a head
    dimension a pool layer for attention.

``loop_weight_bytes`` (under ``loop_weights_roofline``) and
``attention_bytes`` (under ``attn_kv_roofline``) are the two parts of
``decode_step_bytes``, each for the reader that times that part alone. No
tp: the program refuses this block on a mesh.
"""

from __future__ import annotations


def _sizes(cfg: dict) -> dict:
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or h // nh
    return {"layer": [(h * nh * d, nh * d), (h * nkv * d, nkv * d),
                      (h * nkv * d, nkv * d), (nh * d * h, h),
                      (h * i, i), (h * i, i), (i * h, h)],
            "head": [(h * cfg["vocab_size"], cfg["vocab_size"])],
            "norms": 4 * h * 2,                     # four vectors, bf16
            "kv_token_layer": 2 * nkv * d * 2}      # K and V, bf16, a layer


def stored(matrices, quant: str | None) -> float:
    """Bytes of (values, output channels) matrices as stored: int8 values
    and a float32 scale per output channel, or bf16."""
    per_value = 1 if quant == "int8" else 2
    return sum(values * per_value + (4 * out if quant == "int8" else 0)
               for values, out in matrices)


def pool_layers(cfg: dict) -> int:
    """(pass, layer) pairs a token leaves K and V in."""
    return cfg["total_ut_steps"] * cfg["num_hidden_layers"]


def kv_token_bytes(cfg: dict) -> int:
    """K and V of one token over every pool layer, bf16."""
    return pool_layers(cfg) * _sizes(cfg)["kv_token_layer"]


def loop_weight_bytes(cfg: dict, quant: str | None, rows: float = 1
                      ) -> float:
    """Bytes of weights one decode step reads: the layers once a pass, the
    head and the final norm once, an embedding row a sequence."""
    sizes = _sizes(cfg)
    h = cfg["hidden_size"]
    per_value = 1 if quant == "int8" else 2
    layer = stored(sizes["layer"], quant) + sizes["norms"]
    head = stored(sizes["head"], quant)
    if cfg.get("tie_word_embeddings", False):
        head = stored([(h * cfg["vocab_size"], h)], quant)
    return (pool_layers(cfg) * layer + head + h * 2
            + max(1, round(rows)) * h * per_value)


def attention_bytes(cfg: dict, rows: float, context_tokens: float) -> float:
    """Bytes of K and V one decode step moves: every live token's in every
    pool layer read, one token's written a sequence."""
    return (context_tokens + rows) * kv_token_bytes(cfg)


def decode_step_bytes(cfg: dict, quant: str | None, tp: int, rows: float,
                      context_tokens: float) -> float:
    if tp != 1:
        raise ValueError("a looped stack is served on one device")
    return (loop_weight_bytes(cfg, quant, rows)
            + attention_bytes(cfg, rows, context_tokens))


def decode_step_flops(cfg: dict, tp: int, rows: float,
                      context_tokens: float) -> float:
    if tp != 1:
        raise ValueError("a looped stack is served on one device")
    sizes = _sizes(cfg)
    nh = cfg["num_attention_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // nh
    visited = (pool_layers(cfg) * sum(v for v, _ in sizes["layer"])
               + sizes["head"][0][0])
    attn = 4 * pool_layers(cfg) * nh * d * context_tokens
    return 2 * visited * rows + attn
