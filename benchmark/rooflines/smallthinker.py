"""Bytes and operations of one decode step of the SmallThinker block, from
shapes alone, on one chip: lib/roofline.py's reckoning with the dense
feed-forward replaced by a router (hidden x experts, bf16) and experts of
their own width (``moe_ffn_hidden_size``).

Two counts of the expert bytes, kept apart:
  * ``decode_step_bytes``, the floor under ``decode_window_roofline``, is
    lib/roofline.py's own definition carried over: this chip's weights AS
    STORED, every resident expert of every layer. It is what a step reads
    that streams the experts it holds, as the program's masked product
    does; it takes no count and no expectation of how rows route. A step
    has to read an expert's three matrices only if some row chose it, so a
    program that skips the others reads LESS than this floor and its share
    would pass 100 %: the floor then has to take the program's count
    (lib/roofline.py hands this function rows and context only; PERF.md
    section 7).
  * ``expert_layer_bytes(cfg, quant, touched)``, under ``moe_roofline``:
    the router and ``touched`` experts' matrices, ``touched`` the program's
    own count of distinct experts a layer-step's live rows chose.
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
                      context_tokens: float) -> float:
    if tp != 1:
        raise ValueError("the SmallThinker block is served on one device")
    sizes = _sizes(cfg)
    h = cfg["hidden_size"]
    per_value = 1 if quant == "int8" else 2
    layer = (stored(sizes["attention"], quant) + 2 * h * 2   # two norms
             + expert_layer_bytes(cfg, quant, cfg["moe_num_primary_experts"]))
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
