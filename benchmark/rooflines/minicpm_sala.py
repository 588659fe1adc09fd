"""Operations and bytes of one decode step of the MiniCPM-SALA block
(``minicpm_sala``): every layer a mixer by ``mixer_types`` (a lightning
linear-attention mixer, or attention over the chosen blocks of keys) and a
dense SwiGLU feed-forward, an untied head.

One decode step must at least
  * read every weight once, as stored (int8 values and their float32
    scales; norms bf16), and of the embedding one row a sequence;
  * read AND write the recurrent state of every live row in every lightning
    layer (``state_bytes_per_row``: S [heads, head_dim, head_dim] float32, a
    layer; no convolution);
  * in the attention layers ALONE (the others leave nothing a token): read
    the compressed keys of every live token once (the mean of every
    ``kernel_stride`` keys as the pool holds it: ``head_dim * 2 /
    kernel_stride`` bytes a token a KV head), read the K and V of the KEPT
    blocks' keys (at most ``topk * block_size`` a row and KV group), and
    write one token's worth a row;
  * do 2 operations a weight a row, 6 a state element a row (the decay, the
    outer product's multiply and add, the read by q), 2 a compressed key a
    query head and head dimension, and the attention's 4 a kept key a head
    dimension.

``ssm_layer_bytes`` is the lightning layers' part of that, ``index_counts``
the compressed scores', ``sparse_attention_counts`` the kept blocks': each
counted from the program's counters (``ssm_row_steps``, ``attn_context``,
``attn_selected`` a step), whatever implements them, so a program that
updates dead slots, or reads a block it did not keep, reads low against it.
"""

from __future__ import annotations

_SPARSE = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
           "topk": 64, "init_blocks": 1, "window_size": 2048}


def sparse(cfg: dict) -> dict:
    """The six constants of the choice: the file's, else the family's."""
    return {**_SPARSE, **(cfg.get("sparse_config") or {})}


def kinds(cfg: dict) -> dict:
    """How many layers have each mixer."""
    mixers = cfg["mixer_types"]
    return {"L": mixers.count("lightning-attn"),
            "S": mixers.count("minicpm4")}


def _sizes(cfg: dict) -> dict:
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    inner = cfg["lightning_nh"] * cfg["lightning_head_dim"]
    return {
        # (values, output channels) of each matrix
        "lightning": [(h * 4 * inner, 4 * inner), (inner * h, h)],
        # bf16 values of a lightning mixer beside them: the norms of a
        # head's q and k and of the output.
        "lightning_small": 2 * cfg["lightning_head_dim"] + inner,
        "attention": [(h * nh * d, nh * d), (h * nkv * d, nkv * d),
                      (h * nkv * d, nkv * d), (h * nh * d, nh * d),
                      (nh * d * h, h)],
        "attention_small": 2 * d,
        "mlp": [(h * i, i), (h * i, i), (i * h, h)],
        "head": [(h * cfg["vocab_size"], cfg["vocab_size"])],
    }


def stored(matrices, quant: str | None) -> float:
    """Bytes of (values, output channels) matrices as stored: int8 values
    and a float32 scale per output channel, or bf16."""
    per_value = 1 if quant == "int8" else 2
    return sum(values * per_value + (4 * out if quant == "int8" else 0)
               for values, out in matrices)


def state_bytes_per_row(cfg: dict) -> int:
    """Bytes of recurrent state ONE row holds over all lightning layers: S
    in float32 a layer."""
    return (kinds(cfg)["L"] * 4 * cfg["lightning_nh"]
            * cfg["lightning_head_dim"] ** 2)


def kv_bytes_per_token(cfg: dict) -> int:
    """K, V and the compressed-key array's bytes of one token (bf16), over
    the attention layers alone."""
    per_head = 2 * cfg["head_dim"] * 2 + compressed_bytes_per_token(cfg)
    return kinds(cfg)["S"] * cfg["num_key_value_heads"] * per_head


def compressed_bytes_per_token(cfg: dict) -> float:
    """What a token adds to the compressed-key array, a KV head a layer."""
    return cfg["head_dim"] * 2 / sparse(cfg)["kernel_stride"]


def ssm_layer_bytes(cfg: dict, quant: str | None, row_steps: float) -> float:
    """Bytes ONE decode step's lightning layers move, all of them together:
    their weights as stored (the two projections, the small bf16 leaves,
    the norm ahead of the mixer) and the state of ``row_steps`` live rows
    read and written."""
    sizes = _sizes(cfg)
    weights = kinds(cfg)["L"] * (stored(sizes["lightning"], quant)
                                 + 2 * (sizes["lightning_small"]
                                        + cfg["hidden_size"]))
    return weights + 2 * row_steps * state_bytes_per_row(cfg)


def index_counts(cfg: dict, quant: str | None, rows: float,
                 context_keys: float) -> tuple[float, float]:
    """(bytes, operations) of ONE decode step's compressed scores over all
    attention layers: ``context_keys`` is the program's count of the keys
    in context, summed over live rows and layers; a key's share of the
    compressed keys is read once a KV head and scored by every query head.
    The choice has no matrices of its own (``quant`` and ``rows`` are the
    reader's signature)."""
    del quant, rows
    per_key = cfg["head_dim"] / sparse(cfg)["kernel_stride"]
    return (context_keys * cfg["num_key_value_heads"] * per_key * 2,
            2.0 * context_keys * cfg["num_attention_heads"] * per_key)


def sparse_attention_counts(cfg: dict, selected_keys: float
                            ) -> tuple[float, float]:
    """(bytes, operations) of ONE decode step's attention over the kept
    blocks: ``selected_keys`` is the program's count of the keys attended,
    summed over live rows and layers (a KV group's mean); K and V of each
    are read once a KV head and scored and weighed by every query head."""
    d = cfg["head_dim"]
    return (selected_keys * cfg["num_key_value_heads"] * 2 * d * 2,
            4.0 * selected_keys * cfg["num_attention_heads"] * d)


def kept_keys(cfg: dict, rows: float, context_tokens: float) -> float:
    """The most keys a step's rows can keep of ``context_tokens`` in
    context: every one, or ``topk`` blocks a row. The harness hands the
    step's floor the rows and the SUM of their contexts, no row's own
    depth and not the program's ``attn_selected``, so for rows of mixed
    depth this counts high (a row of 2,000 keys beside one of 8,000 keeps
    6,096, not 8,192): in the cell 85 % of the keys where the program
    counted 80 (16 rows, call 3), 0.03 GB of a step's 11.4, so
    ``decode_window_roofline`` reads 0.3 % high. ``attn_sparse_roofline`` is not touched: its reader
    feeds ``sparse_attention_counts`` the program's own count."""
    s = sparse(cfg)
    return min(context_tokens, rows * s["topk"] * s["block_size"])


def decode_step_bytes(cfg: dict, quant: str | None, tp: int, rows: float,
                      context_tokens: float) -> float:
    if tp != 1:
        raise ValueError("the MiniCPM-SALA block is served on one device")
    sizes = _sizes(cfg)
    h, n = cfg["hidden_size"], kinds(cfg)
    layers = n["L"] + n["S"]
    per_value = 1 if quant == "int8" else 2
    attention = n["S"] * (stored(sizes["attention"], quant)
                          + 2 * (sizes["attention_small"] + h))
    mlp = layers * (stored(sizes["mlp"], quant) + h * 2)
    head = stored(sizes["head"], quant) + h * 2             # final norm
    per_layer_key = cfg["num_key_value_heads"] * 2 * cfg["head_dim"] * 2
    pool = n["S"] * (
        (kept_keys(cfg, rows, context_tokens) + rows) * per_layer_key
        + context_tokens * cfg["num_key_value_heads"]
        * compressed_bytes_per_token(cfg))
    embed = max(1, round(rows)) * h * per_value
    return (ssm_layer_bytes(cfg, quant, rows) + attention + mlp + head
            + pool + embed)


def decode_step_flops(cfg: dict, tp: int, rows: float,
                      context_tokens: float) -> float:
    """What the step has to compute: a row through every mixer's
    projections, every feed-forward and the head, its state (6 operations
    an element), and over the keys: the compressed scores of every key in
    context and the attention's products over the kept ones."""
    if tp != 1:
        raise ValueError("the MiniCPM-SALA block is served on one device")
    sizes = _sizes(cfg)
    n = kinds(cfg)
    values = lambda ms: sum(v for v, _ in ms)  # noqa: E731
    per_row = (n["L"] * values(sizes["lightning"])
               + n["S"] * values(sizes["attention"])
               + (n["L"] + n["S"]) * values(sizes["mlp"])
               + values(sizes["head"]))
    state = state_bytes_per_row(cfg) / 4
    keys = (index_counts(cfg, None, rows, n["S"] * context_tokens)[1]
            + sparse_attention_counts(
                cfg, n["S"] * kept_keys(cfg, rows, context_tokens))[1])
    return rows * (2 * per_row + 6 * state) + keys
