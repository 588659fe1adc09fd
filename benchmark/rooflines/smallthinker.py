"""Bytes and operations of one decode step of the SmallThinker block, from
shapes alone, on one chip: lib/roofline.py's reckoning with the dense
feed-forward replaced by a router (hidden x experts, bf16) and experts of
their own width (``moe_ffn_hidden_size``).

One count of the expert bytes, ``expert_layer_bytes(cfg, quant, touched)``:
the router and ``touched`` experts' matrices. A step has to read an expert's
three matrices only if some row chose it, so
  * ``decode_step_bytes(..., touched=None)``, the floor under
    ``decode_window_roofline``, takes the program's own count of distinct
    experts a layer-step's live rows chose (``touched``, a mean over the
    ``expert_layers``; lib/roofline.py ``decode_step_floor`` hands it on
    where the program reports one): what a step MUST read, so a program
    that skips the experts no row chose still reads at most 100 %. Without
    a count (``None``) it is every resident expert of every layer, which is
    what the program's masked product streams; the attention, norms, head
    and K and V are counted whole either way;
  * ``moe_roofline`` takes the same function and the same count for the
    expert layers alone.
Even routing would touch E * (1 - (1 - k/E) ** rows) experts, 83 % at 18
rows; the chip's counters read 66 % on random weights (PERF.md section 6),
so no function here takes that expectation.

Window layers read at most ``sliding_window_size`` tokens of a row's K and
V; the mean context a row is all this function is given, so the bound uses
min(context per row, window) for them, which is exact while no row passes
the window. No tp: the program refuses this block on a mesh.
"""

from __future__ import annotations


def _sizes(cfg: dict) -> dict:
    h, i = cfg["hidden_size"], cfg["moe_ffn_hidden_size"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or h // nh
    return {"attention": [(h * nh * d, nh * d), (h * nkv * d, nkv * d),
                          (h * nkv * d, nkv * d), (nh * d * h, h)],
            "expert": [(h * i, i), (h * i, i), (i * h, h)],
            "head": [(h * cfg["vocab_size"], cfg["vocab_size"])],
            "kv_token_layer": 2 * nkv * d * 2}     # K and V, bf16, one layer


def stored(matrices, quant: str | None) -> float:
    """Bytes of (values, output channels) matrices as stored: int8 values
    and a float32 scale per output channel, or bf16."""
    per_value = 1 if quant == "int8" else 2
    return sum(values * per_value + (4 * out if quant == "int8" else 0)
               for values, out in matrices)


def expert_layer_bytes(cfg: dict, quant: str | None, touched: float
                       ) -> float:
    """Bytes ONE expert layer reads in a step that touches ``touched`` of
    its experts: the router and those experts' matrices."""
    return (cfg["hidden_size"] * cfg["moe_num_primary_experts"] * 2
            + touched * stored(_sizes(cfg)["expert"], quant))


def expert_layers(cfg: dict) -> int:
    """Expert layers of the model: what a count of touched experts is a
    mean over, and what ``moe_roofline`` multiplies a layer's bytes by."""
    return cfg["num_hidden_layers"]


def experts_read(cfg: dict, touched: float | None) -> float:
    """Held experts ONE expert layer reads in a step: every one it holds
    where no count is given, else the count, and a step cannot touch more
    experts than it has (nor fewer than none)."""
    held = cfg["moe_num_primary_experts"]
    return held if touched is None else min(max(float(touched), 0.0), held)


def kv_tokens_read(cfg: dict, rows: float, context_tokens: float) -> float:
    """(layer, token) pairs of K and V a step reads: every live token in a
    full layer, at most the window's a row in a window layer."""
    layers = cfg["num_hidden_layers"]
    layout = cfg.get("sliding_window_layout") or [0] * layers
    window = cfg.get("sliding_window_size")
    per_row = context_tokens / rows if rows else 0.0
    seen = min(per_row, window) if window else per_row
    windowed = sum(1 for v in layout if v)
    return (layers - windowed) * context_tokens + windowed * seen * rows


def decode_step_bytes(cfg: dict, quant: str | None, tp: int, rows: float,
                      context_tokens: float, touched: float | None = None
                      ) -> float:
    if tp != 1:
        raise ValueError("the SmallThinker block is served on one device")
    sizes = _sizes(cfg)
    h = cfg["hidden_size"]
    per_value = 1 if quant == "int8" else 2
    layer = (stored(sizes["attention"], quant) + 2 * h * 2   # two norms
             + expert_layer_bytes(cfg, quant, experts_read(cfg, touched)))
    return (cfg["num_hidden_layers"] * layer + stored(sizes["head"], quant)
            + h * 2                                          # final norm
            + max(1, round(rows)) * h * per_value            # embedding rows
            + (kv_tokens_read(cfg, rows, context_tokens)
               + rows * cfg["num_hidden_layers"]) * sizes["kv_token_layer"])


def decode_step_flops(cfg: dict, tp: int, rows: float,
                      context_tokens: float) -> float:
    if tp != 1:
        raise ValueError("the SmallThinker block is served on one device")
    sizes = _sizes(cfg)
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    d = cfg.get("head_dim") or h // nh
    values = lambda ms: sum(v for v, _ in ms)  # noqa: E731
    per_row = (cfg["num_hidden_layers"]
               * (values(sizes["attention"])
                  + h * cfg["moe_num_primary_experts"]
                  + cfg["moe_num_active_primary_experts"]
                  * values(sizes["expert"]))
               + values(sizes["head"]))
    return (2 * per_row * rows
            + 4 * nh * d * kv_tokens_read(cfg, rows, context_tokens))
