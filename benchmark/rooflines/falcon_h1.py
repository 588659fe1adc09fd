"""Operations and bytes of one decode step of the Falcon-H1 block
(``falcon_h1``) on one chip: every layer a Mamba-2 mixer AND an attention
layer side by side on one normed input, then a dense SwiGLU; an untied head.

One decode step must at least
  * read every weight once, as stored (int8 values and their float32 scales;
    the norms, the taps, the convolution's bias, a head's vectors and the
    gated norm's weight bf16), and of the embedding one row a sequence;
  * read AND write the recurrent state of every live row in EVERY layer
    (``state_bytes_per_row``: S [heads, head_dim, state] float32 and the
    convolution's last ``mamba_d_conv - 1`` inputs in bfloat16);
  * read the K and V of every live token in EVERY layer and write one
    token's worth a row;
  * do 2 operations a weight a row, 6 a state element a row (the decay, the
    outer product's multiply and add, the read by C) and the attention's 4
    a key a head dimension.

Each part for the reader that times it alone, the work counted from the rows
that were LIVE (the program's counter ``ssm_row_steps`` a step) and the
tokens in context, whatever implements it: ``ssm_layer_bytes`` (every SSM
branch's weights + 2 x the state, under ``ssm_roofline``), ``state_bytes``
(the float32 state alone, read once and written once, under
``ssm_state_roofline``), ``attention_bytes`` (K and V, under
``attn_kv_roofline``), ``mixer_bytes`` (both branches' weights, the state
and the K/V, under ``mixers_roofline``). No tp: the program refuses this
block on a mesh.
"""

from __future__ import annotations


def _sizes(cfg: dict) -> dict:
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    heads = cfg["mamba_n_heads"]
    inner = heads * cfg["mamba_d_head"]
    chan = inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    w_in = inner + chan + heads                 # z | x | B | C | dt
    return {
        # (values, output channels) of each int8 matrix
        "ssm": [(h * w_in, w_in), (inner * h, h)],
        # bf16 values of an SSM branch beside them: the taps, the
        # convolution's bias, dt's bias, A_log and D a head, the gated
        # norm's weight.
        "ssm_small": (cfg["mamba_d_conv"] + 1) * chan + 3 * heads + inner,
        "attention": [(h * nh * d, nh * d), (h * nkv * d, nkv * d),
                      (h * nkv * d, nkv * d), (nh * d * h, h)],
        "mlp": [(h * i, i), (h * i, i), (i * h, h)],
        "head": [(h * cfg["vocab_size"], cfg["vocab_size"])],
    }


def stored(matrices, quant: str | None) -> float:
    """Bytes of (values, output channels) matrices as stored: int8 values
    and a float32 scale per output channel, or bf16."""
    per_value = 1 if quant == "int8" else 2
    return sum(values * per_value + (4 * out if quant == "int8" else 0)
               for values, out in matrices)


def layer_values(cfg: dict) -> int:
    """Parameters of ONE layer (what engine/model.py ``param_shapes``
    stacks a layer): both branches, the feed-forward, two norms."""
    sizes = _sizes(cfg)
    matrices = sizes["ssm"] + sizes["attention"] + sizes["mlp"]
    return (sum(v for v, _ in matrices) + sizes["ssm_small"]
            + 2 * cfg["hidden_size"])


def resident_values(cfg: dict) -> int:
    """Parameters resident on the chip: the layers, both tables, the final
    norm."""
    return (cfg["num_hidden_layers"] * layer_values(cfg)
            + 2 * cfg["vocab_size"] * cfg["hidden_size"]
            + cfg["hidden_size"])


def state_bytes_per_row(cfg: dict) -> int:
    """Bytes of recurrent state ONE row holds over all layers: S in float32
    and the convolution's last inputs in bfloat16, a layer."""
    inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    chan = inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return cfg["num_hidden_layers"] * (
        4 * inner * cfg["mamba_d_state"]
        + 2 * (cfg["mamba_d_conv"] - 1) * chan)


def state_bytes(cfg: dict, row_steps: float) -> float:
    """Bytes the float32 state S of ``row_steps`` live rows moves in ONE
    decode step over all layers: read once, written once, whatever
    implements the update."""
    inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    return (2 * row_steps * cfg["num_hidden_layers"] * 4 * inner
            * cfg["mamba_d_state"])


def kv_bytes_per_token(cfg: dict) -> int:
    """K and V bytes of one token (bf16), every layer."""
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * 2)


def ssm_weight_bytes(cfg: dict, quant: str | None) -> float:
    """Every SSM branch's weights as stored (the shared norm ahead of both
    branches counted here)."""
    sizes = _sizes(cfg)
    return cfg["num_hidden_layers"] * (
        stored(sizes["ssm"], quant)
        + 2 * (sizes["ssm_small"] + cfg["hidden_size"]))


def attention_weight_bytes(cfg: dict, quant: str | None) -> float:
    return cfg["num_hidden_layers"] * stored(_sizes(cfg)["attention"], quant)


def ssm_layer_bytes(cfg: dict, quant: str | None, row_steps: float) -> float:
    """Bytes ONE decode step's SSM branches move, all of them together:
    their weights as stored and the state of ``row_steps`` live rows read
    and written."""
    return (ssm_weight_bytes(cfg, quant)
            + 2 * row_steps * state_bytes_per_row(cfg))


def attention_bytes(cfg: dict, rows: float, context_tokens: float) -> float:
    """Bytes of K and V one decode step moves: every live token's in every
    layer read, one token's written a sequence."""
    return (context_tokens + rows) * kv_bytes_per_token(cfg)


def mixer_bytes(cfg: dict, quant: str | None, row_steps: float, rows: float,
                context_tokens: float) -> float:
    """Bytes ONE decode step's two branches move in every layer: both
    branches' weights as stored, the state of the live rows read and
    written, the K and V in context read and a token's written."""
    return (ssm_layer_bytes(cfg, quant, row_steps)
            + attention_weight_bytes(cfg, quant)
            + attention_bytes(cfg, rows, context_tokens))


def decode_step_bytes(cfg: dict, quant: str | None, tp: int, rows: float,
                      context_tokens: float) -> float:
    if tp != 1:
        raise ValueError("the Falcon-H1 block is served on one device")
    sizes = _sizes(cfg)
    h = cfg["hidden_size"]
    per_value = 1 if quant == "int8" else 2
    mlp = cfg["num_hidden_layers"] * (stored(sizes["mlp"], quant) + h * 2)
    head = stored(sizes["head"], quant) + h * 2             # final norm
    embed = max(1, round(rows)) * h * per_value
    return (mixer_bytes(cfg, quant, rows, rows, context_tokens) + mlp + head
            + embed)


def decode_step_flops(cfg: dict, tp: int, rows: float,
                      context_tokens: float) -> float:
    """What the step has to compute: a row through both branches'
    projections, its state (6 operations an element), the feed-forward and
    the head; and the attention's products over every key in context."""
    if tp != 1:
        raise ValueError("the Falcon-H1 block is served on one device")
    sizes = _sizes(cfg)
    layers = cfg["num_hidden_layers"]
    values = lambda ms: sum(v for v, _ in ms)  # noqa: E731
    per_row = (layers * (values(sizes["ssm"]) + values(sizes["attention"])
                         + values(sizes["mlp"]))
               + values(sizes["head"]))
    state = (layers * cfg["mamba_n_heads"] * cfg["mamba_d_head"]
             * cfg["mamba_d_state"])
    attn = (4 * layers * cfg["num_attention_heads"] * cfg["head_dim"]
            * context_tokens)
    return rows * (2 * per_row + 6 * state) + attn
