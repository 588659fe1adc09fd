"""Operations and bytes of one decode step of the Solar-Open2 block
(``solar_open2``) on one chip's share: every layer a mixer (a gated
delta-rule recurrence K, or gated attention without a rotary embedding * where
the layer is among ``gqa_layers``) and an expert layer that holds
``n_routed_experts`` of the router's ``expert_parallel.routed_experts`` and
one shared expert; an untied head.

One decode step must at least
  * read every weight of the share once, as stored (int8 values and their
    float32 scales; norms, router, selection bias, the taps, A_log and
    dt_bias bf16), of the held experts those some row chose
    (``decode_step_bytes``'s ``touched``: the program's count of distinct
    held experts a layer-step's live rows chose, a mean over the layers,
    handed on by lib/roofline.py ``decode_step_floor``; EVERY held expert
    where no count is given), and of the embedding one row a sequence;
  * read AND write the recurrent state of every live row in every K layer
    (``state_bytes_per_row``: S [heads, head_dim, head_dim] float32 and the
    convolution's last ``short_conv_kernel_size - 1`` inputs in bfloat16);
  * read the K and V of every live token in the * layers ALONE and write
    one token's worth a row;
  * do 2 operations a weight a row (of the routed experts: the held share
    of a row's ``num_experts_per_tok`` picks), 9 a state element a row (the
    decay, the read by k, the outer product's multiply and add, the read by
    q, each a multiply and most an add) and the attention's 4 a key a head
    dimension in the * layers.

``ssm_layer_bytes(cfg, quant, row_steps)`` is the K layers' part of that and
``state_bytes(cfg, row_steps)`` the float32 state's alone (what
``ssm_state_roofline`` holds the scope ``ssm.state`` to): the state of the
rows that were LIVE (the program's counter ``ssm_row_steps`` a step) read
ONCE and written ONCE, whatever implements the update: a program that
reads the state a second time for its correction, or updates dead slots,
reads low against it.
"""

from __future__ import annotations


def kinds(cfg: dict) -> dict:
    """How many layers have a mixer of each kind, and the expert layers."""
    layers = cfg["num_hidden_layers"]
    softmax = len(set(cfg["gqa_layers"]) & set(range(layers)))
    return {"K": layers - softmax, "*": softmax, "E": layers}


def _linear(cfg: dict) -> tuple[int, int]:
    linear = cfg["linear_attn_config"]
    return linear["num_heads"], linear["head_dim"]


def _sizes(cfg: dict) -> dict:
    h, ie = cfg["hidden_size"], cfg["moe_intermediate_size"]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    heads, dk = _linear(cfg)
    inner, r = heads * dk, dk           # the low-rank pairs go through dk
    return {
        # (values, output channels) of each matrix
        "mixer": [(h * 3 * inner, 3 * inner), (inner * h, h),
                  (h * r, r), (r * inner, inner),       # the decay's pair
                  (h * r, r), (r * inner, inner),       # the gate's pair
                  (h * heads, heads)],                  # beta
        # bf16 values of a mixer beside them: the taps, dt_bias a channel,
        # A_log a head, the output norm's weight.
        "mixer_small": (cfg["linear_attn_config"]["short_conv_kernel_size"]
                        * 3 * inner + inner + heads + dk),
        "attention": [(h * nh * d, nh * d), (h * nkv * d, nkv * d),
                      (h * nkv * d, nkv * d), (nh * d * h, h)]
        + ([(h * nh * d, nh * d)] if cfg.get("use_gqa_gate") else []),
        "expert": [(h * ie, ie), (h * ie, ie), (ie * h, h)],
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


def state_bytes_per_row(cfg: dict) -> int:
    """Bytes of recurrent state ONE row holds over all K layers: S in
    float32 and the convolution's last inputs in bfloat16, a layer."""
    heads, dk = _linear(cfg)
    taps = cfg["linear_attn_config"]["short_conv_kernel_size"]
    return kinds(cfg)["K"] * (4 * heads * dk * dk
                              + 2 * (taps - 1) * 3 * heads * dk)


def state_bytes(cfg: dict, row_steps: float) -> float:
    """Bytes the float32 state S of ``row_steps`` live rows moves in ONE
    decode step over all K layers: read once, written once."""
    heads, dk = _linear(cfg)
    return 2 * row_steps * kinds(cfg)["K"] * 4 * heads * dk * dk


def kv_bytes_per_token(cfg: dict) -> int:
    """K and V bytes of one token (bf16), over the * layers alone."""
    return (2 * kinds(cfg)["*"] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * 2)


def expert_layer_bytes(cfg: dict, quant: str | None, touched: float
                       ) -> float:
    """Bytes ONE expert layer's routed part reads in a step that touches
    ``touched`` of the experts held here: the router, its selection bias
    and those experts' three matrices."""
    return ((cfg["hidden_size"] + 1) * routed_experts(cfg) * 2
            + touched * stored(_sizes(cfg)["expert"], quant))


def shared_layer_bytes(cfg: dict, quant: str | None) -> float:
    """Bytes ONE expert layer's shared expert holds, read every step."""
    return cfg.get("n_shared_experts", 0) * stored(_sizes(cfg)["expert"],
                                                   quant)


def expert_layers(cfg: dict) -> int:
    """Expert layers of the model: what a count of touched experts is a
    mean over, and what ``moe_roofline`` multiplies a layer's bytes by."""
    return kinds(cfg)["E"]


def experts_read(cfg: dict, touched: float | None) -> float:
    """Held experts ONE expert layer reads in a step: every one it holds
    where no count is given, else the count, and a step cannot touch more
    experts than it has (nor fewer than none)."""
    held = cfg["n_routed_experts"]
    return held if touched is None else min(max(float(touched), 0.0), held)


def ssm_layer_bytes(cfg: dict, quant: str | None, row_steps: float) -> float:
    """Bytes ONE decode step's K layers move, all of them together: their
    weights as stored (every projection, the small bf16 leaves, the norm
    ahead of the mixer) and the state of ``row_steps`` live rows read and
    written."""
    sizes = _sizes(cfg)
    weights = kinds(cfg)["K"] * (stored(sizes["mixer"], quant)
                                 + 2 * (sizes["mixer_small"]
                                        + cfg["hidden_size"]))
    return weights + 2 * row_steps * state_bytes_per_row(cfg)


def decode_step_bytes(cfg: dict, quant: str | None, tp: int, rows: float,
                      context_tokens: float, touched: float | None = None
                      ) -> float:
    if tp != 1:
        raise ValueError("the Solar-Open2 share is served on one device")
    sizes = _sizes(cfg)
    h, n = cfg["hidden_size"], kinds(cfg)
    per_value = 1 if quant == "int8" else 2
    experts = expert_layers(cfg) * (
        expert_layer_bytes(cfg, quant, experts_read(cfg, touched))
        + shared_layer_bytes(cfg, quant) + h * 2)
    attention = n["*"] * (stored(sizes["attention"], quant) + h * 2)
    head = stored(sizes["head"], quant) + h * 2             # final norm
    pool = (context_tokens + rows) * kv_bytes_per_token(cfg)
    embed = max(1, round(rows)) * h * per_value
    return (ssm_layer_bytes(cfg, quant, rows) + experts + attention + head
            + pool + embed)


def decode_step_flops(cfg: dict, tp: int, rows: float,
                      context_tokens: float) -> float:
    """What the step has to compute: a row through every mixer's
    projections and its state (9 operations an element), every attention
    layer's projections, every expert layer's router, its held share of the
    row's picks and the shared expert, and the head; and the attention's
    products over every key in context."""
    if tp != 1:
        raise ValueError("the Solar-Open2 share is served on one device")
    sizes = _sizes(cfg)
    n = kinds(cfg)
    values = lambda ms: sum(v for v, _ in ms)  # noqa: E731
    held_picks = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
                  / routed_experts(cfg))
    expert = (cfg["hidden_size"] * routed_experts(cfg)
              + (held_picks + cfg.get("n_shared_experts", 0))
              * values(sizes["expert"]))
    per_row = (n["K"] * values(sizes["mixer"]) + n["E"] * expert
               + n["*"] * values(sizes["attention"])
               + values(sizes["head"]))
    heads, dk = _linear(cfg)
    state = n["K"] * heads * dk * dk
    attn = (4 * n["*"] * cfg["num_attention_heads"] * cfg["head_dim"]
            * context_tokens)
    return rows * (2 * per_row + 9 * state) + attn
