"""Operations and bytes of one decode step of the Nemotron-H block
(``nemotron_h``) on one chip's share: layers of ONE mixer each, their kind by
``hybrid_override_pattern`` (M a Mamba-2 mixer, E an expert layer that holds
``n_routed_experts`` of the router's ``expert_parallel.routed_experts`` and
one shared expert of its own width, * an attention layer), an untied head.

One decode step must at least
  * read every weight of the share once, as stored (int8 values and their
    float32 scales; norms, router, selection bias, the convolution and a
    head's vectors bf16), of the held experts those some row chose
    (``decode_step_bytes``'s ``touched``: the program's count of distinct
    held experts a layer-step's live rows chose, a mean over the E layers,
    handed on by lib/roofline.py ``decode_step_floor``; EVERY held expert
    where no count is given), and of the embedding one row a sequence;
  * read AND write the recurrent state of every live row in every M layer
    (``state_bytes_per_row``: S [heads, head_dim, state] float32 and the
    convolution's last ``conv_kernel - 1`` inputs in bfloat16, a layer);
  * read the K and V of every live token in the * layers ALONE (the other
    layers leave nothing a token) and write one token's worth a row;
  * do 2 operations a weight a row (of the routed experts: the held share
    of a row's ``num_experts_per_tok`` picks), 6 a state element a row (the
    decay, the outer product's multiply and add, the read by C) and the
    attention's 4 a key a head dimension in the * layers.

``ssm_layer_bytes(cfg, quant, row_steps)`` is the M layers' part of that:
the work is counted from the rows that were LIVE (the program's counter
``ssm_row_steps`` a step), whatever implements it, so a program that
updates dead slots too reads low against it.
"""

from __future__ import annotations


def kinds(cfg: dict) -> dict:
    """How many layers of each kind the pattern holds."""
    pattern = cfg["hybrid_override_pattern"]
    return {kind: pattern.count(kind) for kind in "ME*"}


def _sizes(cfg: dict) -> dict:
    h, ie = cfg["hidden_size"], cfg["moe_intermediate_size"]
    si = cfg["moe_shared_expert_intermediate_size"]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    heads, inner = cfg["mamba_num_heads"], (cfg["mamba_num_heads"]
                                            * cfg["mamba_head_dim"])
    chan = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    wide = inner + chan + heads                 # z | xBC | dt
    return {
        # (values, output channels) of each matrix
        "mixer": [(h * wide, wide), (inner * h, h)],
        # bf16 values of a mixer beside them: the taps, the convolution's
        # bias, dt's bias, A_log and D a head, the gated norm's weight.
        "mixer_small": (cfg["conv_kernel"] + 1) * chan + 3 * heads + inner,
        "attention": [(h * nh * d, nh * d), (h * nkv * d, nkv * d),
                      (h * nkv * d, nkv * d), (nh * d * h, h)],
        "expert": [(h * ie, ie), (ie * h, h)],
        "shared": [(h * si, si), (si * h, h)],
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
    """Bytes of recurrent state ONE row holds over all M layers: S in
    float32 and the convolution's last inputs in bfloat16, a layer."""
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    chan = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    return kinds(cfg)["M"] * (4 * inner * cfg["ssm_state_size"]
                              + 2 * (cfg["conv_kernel"] - 1) * chan)


def kv_bytes_per_token(cfg: dict) -> int:
    """K and V bytes of one token (bf16), over the * layers alone."""
    return (2 * kinds(cfg)["*"] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * 2)


def expert_layer_bytes(cfg: dict, quant: str | None, touched: float
                       ) -> float:
    """Bytes ONE expert layer's routed part reads in a step that touches
    ``touched`` of the experts held here: the router, its selection bias
    and those experts' two matrices."""
    return ((cfg["hidden_size"] + 1) * routed_experts(cfg) * 2
            + touched * stored(_sizes(cfg)["expert"], quant))


def shared_layer_bytes(cfg: dict, quant: str | None) -> float:
    """Bytes ONE expert layer's shared expert holds, read every step."""
    return cfg.get("n_shared_experts", 0) * stored(_sizes(cfg)["shared"],
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
    """Bytes ONE decode step's M layers move, all of them together: their
    weights as stored (the two projections, the small bf16 leaves, the norm
    ahead of the mixer) and the state of ``row_steps`` live rows read and
    written."""
    sizes = _sizes(cfg)
    weights = kinds(cfg)["M"] * (stored(sizes["mixer"], quant)
                                 + 2 * (sizes["mixer_small"]
                                        + cfg["hidden_size"]))
    return weights + 2 * row_steps * state_bytes_per_row(cfg)


def decode_step_bytes(cfg: dict, quant: str | None, tp: int, rows: float,
                      context_tokens: float, touched: float | None = None
                      ) -> float:
    if tp != 1:
        raise ValueError("the Nemotron-H share is served on one device")
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
    """What the step has to compute: a row through every mixer's two
    projections and its state (6 operations an element), every attention
    layer's projections, every expert layer's router, its held share of the
    row's picks and the shared expert, and the head; and the attention's
    products over every key in context."""
    if tp != 1:
        raise ValueError("the Nemotron-H share is served on one device")
    sizes = _sizes(cfg)
    n = kinds(cfg)
    values = lambda ms: sum(v for v, _ in ms)  # noqa: E731
    held_picks = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
                  / routed_experts(cfg))
    expert = (cfg["hidden_size"] * routed_experts(cfg)
              + held_picks * values(sizes["expert"])
              + cfg.get("n_shared_experts", 0) * values(sizes["shared"]))
    per_row = (n["M"] * values(sizes["mixer"]) + n["E"] * expert
               + n["*"] * values(sizes["attention"])
               + values(sizes["head"]))
    state = (n["M"] * cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
             * cfg["ssm_state_size"])
    attn = (4 * n["*"] * cfg["num_attention_heads"] * cfg["head_dim"]
            * context_tokens)
    return rows * (2 * per_row + 6 * state) + attn
