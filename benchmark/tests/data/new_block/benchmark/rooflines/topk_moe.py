"""Bytes and operations of one decode step of a Mixtral-style block, from
shapes alone, on one chip of a tp group: lib/roofline.py's reckoning with
the dense feed-forward replaced by a router (hidden x experts, bf16,
replicated) and the experts. A step reads an expert's three matrices only
if some row chose it: with each row choosing ``num_experts_per_tok`` of
``num_local_experts`` and no expert favoured, that is
E * (1 - (1 - k/E) ** rows) experts a layer, and each row multiplies by k
of them. Experts shard over tp whole (engine/model.py ``param_specs``), so
a chip reads its share of the touched ones.
"""

from __future__ import annotations

from benchmark.lib.roofline import kv_bytes_per_token


def _matrices(cfg: dict) -> dict:
    """(values, output channels) of the matrices one layer's attention
    reads, of one expert, and of the output head."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or h // nh
    return {"attention": [(h * nh * d, nh * d), (h * nkv * d, nkv * d),
                          (h * nkv * d, nkv * d), (nh * d * h, h)],
            "expert": [(h * i, i), (h * i, i), (i * h, h)],
            "head": [(h * cfg["vocab_size"], cfg["vocab_size"])]}


def experts_touched(cfg: dict, rows: float) -> float:
    e, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** rows)


def decode_step_bytes(cfg: dict, quant: str | None, tp: int, rows: float,
                      context_tokens: float) -> float:
    h = cfg["hidden_size"]
    per_value = 1 if quant == "int8" else 2

    def stored(matrices) -> float:
        return sum(values * per_value + (4 * out if quant == "int8" else 0)
                   for values, out in matrices)

    mats = _matrices(cfg)
    layer = (stored(mats["attention"]) / tp
             + 2 * h * 2                                # two norms, bf16
             + h * cfg["num_local_experts"] * 2         # the router
             + experts_touched(cfg, rows) * stored(mats["expert"]) / tp)
    return (cfg["num_hidden_layers"] * layer + stored(mats["head"]) / tp
            + h * 2                                     # final norm
            + max(1, round(rows)) * h * per_value / tp  # embedding rows
            + (context_tokens + rows) * kv_bytes_per_token(cfg, tp))


def decode_step_flops(cfg: dict, tp: int, rows: float,
                      context_tokens: float) -> float:
    mats = _matrices(cfg)
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    values = lambda ms: sum(v for v, _ in ms)  # noqa: E731
    per_row = (cfg["num_hidden_layers"]
               * (values(mats["attention"]) / tp
                  + h * cfg["num_local_experts"]
                  + cfg["num_experts_per_tok"] * values(mats["expert"]) / tp)
               + values(mats["head"]) / tp)
    attn = (4 * cfg["num_hidden_layers"] * nh * (cfg.get("head_dim")
                                                 or h // nh)
            * context_tokens / tp)
    return 2 * per_row * rows + attn
