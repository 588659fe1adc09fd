"""Bytes and operations of one decode step of the Cohere2-MoE block on ONE
chip of an expert-parallel deployment, from shapes alone: lib/roofline.py's
reckoning with the dense feed-forward replaced by a router as wide as the
deployment has experts (``expert_parallel.routed_experts``, bf16), the
experts HELD here (``num_experts``) and the shared experts
(``num_shared_experts``), all of width ``intermediate_size``; one norm a
layer (a parallel block) and a tied head over the vocabulary rows held.

The expert bytes, as rooflines/smallthinker.py counts them:
  * ``expert_layer_bytes(cfg, quant, touched)``: the router and ``touched``
    HELD experts' matrices, ``touched`` the program's own count of distinct
    held experts a layer-step's live rows chose. The shared experts are NOT
    in it (``shared_layer_bytes`` counts them, under
    ``moe_shared_roofline``), so ``moe_roofline`` stays the routed product's
    share of its roofline.
  * ``decode_step_bytes(..., touched=None)``, the floor under
    ``decode_window_roofline``: this chip's weights AS STORED, every shared
    expert of every layer, and of the held experts the ``touched`` a layer
    that some row chose (the same count, handed on by lib/roofline.py
    ``decode_step_floor``); every held expert where no count is given
    (``None``). No function takes an expectation of how rows route.
Even routing would touch held * (1 - (1 - k/routed) ** rows) of the held
experts, 70 % at 19 rows; no function here takes that expectation.

Window layers read at most ``sliding_window`` tokens of a row's K and V; the
mean context a row is all this function is given, so the bound uses
min(context per row, window) for them, which is exact while no row passes
the window. No tp: the program refuses this block on a mesh.
"""

from __future__ import annotations


def _sizes(cfg: dict) -> dict:
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or h // nh
    return {"attention": [(h * nh * d, nh * d), (h * nkv * d, nkv * d),
                          (h * nkv * d, nkv * d), (nh * d * h, h)],
            "expert": [(h * i, i), (h * i, i), (i * h, h)],
            # The tied table, quantised per hidden channel.
            "head": [(h * cfg["vocab_size"], h)],
            "kv_token_layer": 2 * nkv * d * 2}     # K and V, bf16, one layer


def stored(matrices, quant: str | None) -> float:
    """Bytes of (values, output channels) matrices as stored: int8 values
    and a float32 scale per output channel, or bf16."""
    per_value = 1 if quant == "int8" else 2
    return sum(values * per_value + (4 * out if quant == "int8" else 0)
               for values, out in matrices)


def routed_experts(cfg: dict) -> int:
    """The router's width: every expert of the deployment."""
    return (cfg.get("expert_parallel") or {}).get("routed_experts",
                                                  cfg["num_experts"])


def expert_layer_bytes(cfg: dict, quant: str | None, touched: float
                       ) -> float:
    """Bytes ONE expert layer's routed part reads in a step that touches
    ``touched`` of the experts held here: the router and those experts'
    matrices."""
    return (cfg["hidden_size"] * routed_experts(cfg) * 2
            + touched * stored(_sizes(cfg)["expert"], quant))


def shared_layer_bytes(cfg: dict, quant: str | None) -> float:
    """Bytes ONE layer's shared experts hold, every one read every step."""
    return cfg.get("num_shared_experts", 0) * stored(_sizes(cfg)["expert"],
                                                     quant)


def expert_layers(cfg: dict) -> int:
    """Expert layers of the model: what a count of touched experts is a
    mean over, and what ``moe_roofline`` multiplies a layer's bytes by."""
    return cfg["num_hidden_layers"]


def experts_read(cfg: dict, touched: float | None) -> float:
    """Held experts ONE expert layer reads in a step: every one it holds
    where no count is given, else the count, and a step cannot touch more
    experts than it has (nor fewer than none)."""
    held = cfg["num_experts"]
    return held if touched is None else min(max(float(touched), 0.0), held)


def kv_tokens_read(cfg: dict, rows: float, context_tokens: float) -> float:
    """(layer, token) pairs of K and V a step reads: every live token in a
    full layer, at most the window's a row in a window layer."""
    window = cfg.get("sliding_window")
    per_row = context_tokens / rows if rows else 0.0
    seen = min(per_row, window) if window else per_row
    windowed = sum(1 for t in cfg["layer_types"] if t == "sliding_attention")
    return ((cfg["num_hidden_layers"] - windowed) * context_tokens
            + windowed * seen * rows)


def decode_step_bytes(cfg: dict, quant: str | None, tp: int, rows: float,
                      context_tokens: float, touched: float | None = None
                      ) -> float:
    if tp != 1:
        raise ValueError("the Cohere2-MoE share is served on one device")
    sizes = _sizes(cfg)
    h = cfg["hidden_size"]
    per_value = 1 if quant == "int8" else 2
    layer = (stored(sizes["attention"], quant) + h * 2       # the one norm
             + expert_layer_bytes(cfg, quant, experts_read(cfg, touched))
             + shared_layer_bytes(cfg, quant))
    return (cfg["num_hidden_layers"] * layer + stored(sizes["head"], quant)
            + h * 2                                          # final norm
            + max(1, round(rows)) * h * per_value            # embedding rows
            + (kv_tokens_read(cfg, rows, context_tokens)
               + rows * cfg["num_hidden_layers"]) * sizes["kv_token_layer"])


def decode_step_flops(cfg: dict, tp: int, rows: float,
                      context_tokens: float) -> float:
    """What the step has to compute for its rows: the held share of each
    row's chosen experts (k x held / routed of them in the mean), every
    shared expert, the router, attention and the head."""
    if tp != 1:
        raise ValueError("the Cohere2-MoE share is served on one device")
    sizes = _sizes(cfg)
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    d = cfg.get("head_dim") or h // nh
    values = lambda ms: sum(v for v, _ in ms)  # noqa: E731
    held_picks = (cfg["num_experts_per_tok"] * cfg["num_experts"]
                  / routed_experts(cfg))
    per_row = (cfg["num_hidden_layers"]
               * (values(sizes["attention"]) + h * routed_experts(cfg)
                  + (held_picks + cfg.get("num_shared_experts", 0))
                  * values(sizes["expert"]))
               + values(sizes["head"]))
    return (2 * per_row * rows
            + 4 * nh * d * kv_tokens_read(cfg, rows, context_tokens))
