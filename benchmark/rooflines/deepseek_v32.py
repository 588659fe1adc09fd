"""Bytes and operations of one decode step of the DeepSeek-V3.2 block on ONE
chip of an expert-parallel deployment, from shapes alone: the latent
projections (low-rank q, the latent and the shared rope key, Wkv_b as its key
and value halves, the output), the indexer's matrices, a router as wide as the
deployment has experts (``expert_parallel.routed_experts``, bf16, with its
selection bias), the experts HELD here (``n_routed_experts``), the shared
expert, ``first_k_dense_replace`` leading layers with a dense feed-forward of
``intermediate_size`` in place of the expert layer, and an untied head over
the vocabulary columns held. Weights count AS STORED (int8 values and a float32
scale per output channel, or bf16; norms, the router, both biases and the
indexer's head weights bf16).

What a step reads of the pool, a layer: EVERY index key in context (the
indexer scores them all: ``index_head_dim`` bf16 values each) and the latent
entries it attends, at most ``index_topk`` a row (``entry_bytes``: the latent,
the shared rope key and the lane padding the pool stores, 640 bf16 values).

``decode_step_bytes`` / ``decode_step_flops`` are the floor under
``decode_window_roofline``, which hands them the mean rows and the mean TOTAL
live context only. Index keys are exact from that. The chosen entries are
counted as min(context, rows x index_topk) a layer, which is AT OR ABOVE the
true mean (rows are unequal: a short row attends its whole context, a long one
2,048 of it, and the minimum of the sums is no less than the sum of the
minima), so that share can read a little high, never low; at this
configuration the entries are under 5 % of a floor that 8.4 GB of weights
set. ``attn_sparse_roofline`` has the exact count from the program's
``attn_selected`` and is the one that judges the attention.

The expert bytes as rooflines/cohere2_moe.py counts them:
``expert_layer_bytes(cfg, quant, touched)`` is the router and ``touched`` held
experts, the shared expert is ``shared_layer_bytes``, and
``decode_step_bytes(..., touched=None)`` takes the program's count of held
experts a layer-step touched for its ``expert_layers`` (every held expert
where no count is given); the leading dense layers, routers, selection biases
and shared experts are counted whole. No tp: the program refuses this block
on a mesh.
"""

from __future__ import annotations


def _sizes(cfg: dict) -> dict:
    h, i, ie = (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["moe_intermediate_size"])
    nh, qr, r = (cfg["num_attention_heads"], cfg["q_lora_rank"],
                 cfg["kv_lora_rank"])
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]
    return {
        # (values, output channels) of each matrix
        "attention": [(h * qr, qr), (qr * nh * (nope + rope),
                                     nh * (nope + rope)),
                      (h * (r + rope), r + rope), (r * nh * nope, nh * nope),
                      (r * nh * v, nh * v), (nh * v * h, h)],
        "indexer": [(qr * ih * idim, ih * idim), (h * idim, idim)],
        "indexer_bf16": h * ih + 2 * idim,     # head weights, LayerNorm g, b
        "norms": 2 * h + qr + r,               # bf16 vectors of a layer
        "expert": [(h * ie, ie), (h * ie, ie), (ie * h, h)],
        "dense": [(h * i, i), (h * i, i), (i * h, h)],
        "head": [(h * cfg["vocab_size"], cfg["vocab_size"])],
    }


def stored(matrices, quant: str | None) -> float:
    """Bytes of (values, output channels) matrices as stored: int8 values
    and a float32 scale per output channel, or bf16."""
    per_value = 1 if quant == "int8" else 2
    return sum(values * per_value + (4 * out if quant == "int8" else 0)
               for values, out in matrices)


def routed_experts(cfg: dict) -> int:
    """The router's width: every expert of the deployment."""
    return (cfg.get("expert_parallel") or {}).get("routed_experts",
                                                  cfg["n_routed_experts"])


def entry_bytes(cfg: dict) -> int:
    """Bytes ONE latent entry holds in ONE layer of the pool: the latent,
    the shared rope key, and zeros up to the next multiple of 128 lanes
    (bf16)."""
    used = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return -(-used // 128) * 128 * 2


def index_key_bytes(cfg: dict) -> int:
    """Bytes ONE index key holds in ONE layer of the pool (bf16)."""
    return cfg["index_head_dim"] * 2


def index_layer_bytes(cfg: dict, quant: str | None) -> float:
    """Bytes ONE layer's indexer holds in matrices, read once a step."""
    sizes = _sizes(cfg)
    return stored(sizes["indexer"], quant) + sizes["indexer_bf16"] * 2


def index_counts(cfg: dict, quant: str | None, rows: float,
                 context_keys: float) -> tuple[float, float]:
    """(bytes, operations) of ONE step's indexers over every layer:
    ``context_keys`` is the program's ``attn_context`` a step ((row, layer,
    key) triples in context); each is read once (``index_key_bytes``) and
    scored by every index head (a product of index_head_dim, a relu and a
    weighted sum). ``rows`` rows make their query heads and weights from
    each layer's matrices."""
    sizes = _sizes(cfg)
    layers = cfg["num_hidden_layers"]
    values = sum(v for v, _ in sizes["indexer"]) \
        + cfg["hidden_size"] * cfg["index_n_heads"]
    per_key = cfg["index_n_heads"] * (2 * cfg["index_head_dim"] + 2)
    return (layers * index_layer_bytes(cfg, quant)
            + context_keys * index_key_bytes(cfg),
            2 * values * layers * rows + per_key * context_keys)


def sparse_attention_counts(cfg: dict, selected_keys: float
                            ) -> tuple[float, float]:
    """(bytes, operations) of ONE step's attention over the entries it
    chose: ``selected_keys`` is the program's ``attn_selected`` a step
    ((row, layer, key) triples attended). An entry is read once
    (``entry_bytes``); every head scores it in the latent's space (the
    absorbed form: kv_lora_rank + qk_rope_head_dim products) and weighs the
    latent (kv_lora_rank)."""
    nh, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    per_key = nh * (2 * (r + cfg["qk_rope_head_dim"]) + 2 * r)
    return selected_keys * entry_bytes(cfg), per_key * selected_keys


def expert_layer_bytes(cfg: dict, quant: str | None, touched: float
                       ) -> float:
    """Bytes ONE expert layer's routed part reads in a step that touches
    ``touched`` of the experts held here: the router, its selection bias
    and those experts' matrices."""
    return ((cfg["hidden_size"] + 1) * routed_experts(cfg) * 2
            + touched * stored(_sizes(cfg)["expert"], quant))


def shared_layer_bytes(cfg: dict, quant: str | None) -> float:
    """Bytes ONE expert layer's shared expert holds, read every step."""
    return cfg.get("n_shared_experts", 0) * stored(_sizes(cfg)["expert"],
                                                   quant)


def expert_layers(cfg: dict) -> int:
    """Expert layers of the model: what a count of touched experts is a
    mean over, and what ``moe_roofline`` multiplies a layer's bytes by."""
    return cfg["num_hidden_layers"] - cfg.get("first_k_dense_replace", 0)


def experts_read(cfg: dict, touched: float | None) -> float:
    """Held experts ONE expert layer reads in a step: every one it holds
    where no count is given, else the count, and a step cannot touch more
    experts than it has (nor fewer than none)."""
    held = cfg["n_routed_experts"]
    return held if touched is None else min(max(float(touched), 0.0), held)


def _selected(cfg: dict, rows: float, context_tokens: float) -> float:
    """Entries a layer's attention reads, from the means alone: at or above
    the true mean (the module's docstring)."""
    return min(context_tokens, rows * cfg["index_topk"])


def decode_step_bytes(cfg: dict, quant: str | None, tp: int, rows: float,
                      context_tokens: float, touched: float | None = None
                      ) -> float:
    if tp != 1:
        raise ValueError("the DeepSeek-V3.2 share is served on one device")
    sizes = _sizes(cfg)
    h, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    dense = cfg.get("first_k_dense_replace", 0)
    per_value = 1 if quant == "int8" else 2
    every = (stored(sizes["attention"], quant) + sizes["norms"] * 2
             + index_layer_bytes(cfg, quant))
    expert = (expert_layer_bytes(cfg, quant, experts_read(cfg, touched))
              + shared_layer_bytes(cfg, quant))
    pool = layers * (
        _selected(cfg, rows, context_tokens) * entry_bytes(cfg)
        + context_tokens * index_key_bytes(cfg)
        + rows * (entry_bytes(cfg) + index_key_bytes(cfg)))   # written
    return (layers * every + expert_layers(cfg) * expert
            + dense * stored(sizes["dense"], quant)
            + stored(sizes["head"], quant) + h * 2            # final norm
            + max(1, round(rows)) * h * per_value             # embedding rows
            + pool)


def decode_step_flops(cfg: dict, tp: int, rows: float,
                      context_tokens: float) -> float:
    """What the step has to compute for its rows: the latent projections and
    the indexer's, the held share of each row's chosen experts (k x held /
    routed of them in the mean), the shared expert, the router, a dense
    feed-forward in the leading layers, the head; the indexer over every
    key in context and the attention over the chosen entries."""
    if tp != 1:
        raise ValueError("the DeepSeek-V3.2 share is served on one device")
    sizes = _sizes(cfg)
    h, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    dense = cfg.get("first_k_dense_replace", 0)
    values = lambda ms: sum(v for v, _ in ms)  # noqa: E731
    held_picks = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
                  / routed_experts(cfg))
    per_row = (layers * values(sizes["attention"])
               + (layers - dense) * (
                   h * routed_experts(cfg)
                   + (held_picks + cfg.get("n_shared_experts", 0))
                   * values(sizes["expert"]))
               + dense * values(sizes["dense"]) + values(sizes["head"]))
    _, index_ops = index_counts(cfg, None, rows, layers * context_tokens)
    _, attn_ops = sparse_attention_counts(
        cfg, layers * _selected(cfg, rows, context_tokens))
    return 2 * per_row * rows + index_ops + attn_ops
