"""Operations and bytes of one DRAFTING decode step of the GLM-4.7-Flash block
(``glm4_moe_lite``) on one chip's share: latent attention without an indexer
(every key in context attended), a leading dense layer, expert layers that hold
``n_routed_experts`` of the router's ``expert_parallel.routed_experts``, one
shared expert, an untied head, and the model's own prediction module as the
draft of every step (``launch.spec_decode`` "mtp", ``spec_k`` 1).

One scan step of the window program is ONE verify of k + 1 positions a row and
ONE run of the module (engine/runner.py ``_get_mtp_window``). It must at least
  * read every weight of the share once, as stored (int8 values and their
    float32 scales; norms, router and bias bf16): the model's layers, the
    module's (``draft_module_bytes``), and the head TWICE (once for the k + 1
    verified positions together, once for the module's draft); of the held
    experts, those some position chose (``decode_step_bytes``'s ``touched``,
    handed on by lib/roofline.py ``decode_step_floor``; EVERY held expert
    where no count is given);
  * read each live row's latent entries (``entry_bytes`` a token a layer) over
    the model's layers and the module's ONCE, whatever k (one walk of the
    pool serves every query position), and write the entries of the positions
    it commits;
  * do 2 operations a weight a POSITION: a row's k + 1 verified positions
    through the model, the positions emitted through the module, k + 1 rows
    of the head and one more for the draft; and the attention's products a
    position a key.
Without ``launch.spec_decode`` the same counts at k = 0 and no module.

``touched`` is the program's own count of distinct held experts a layer-step's
live positions chose, a mean over the layer-steps the program counted. The
drafting window counts the MODULE's expert layer beside the model's 46
(engine/runner.py ``_get_mtp_window``: ``counted = counts.sum(0) +
mcounts``, the verify's per-layer sums plus ``mtp_block``'s), so the mean is
over 47 layers a step and the count replaces the held experts in all 47
(``counted_expert_layers``): 47 x the mean is the program's sum to the
expert. ``expert_layers`` is the model's 46 alone, the ones under the ``moe``
scope that ``moe_roofline`` times (the module's runs under ``mtp``).

``sparse_attention_counts(cfg, keys)``: ``keys`` is the program's
``attn_selected`` a step: for this block a live row's keys in context counted
ONCE a step and layer (48 layers: the module's too). The bytes are charged
once a key, the products k + 1 times.
"""

from __future__ import annotations


def _sizes(cfg: dict) -> dict:
    h, i, ie = (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["moe_intermediate_size"])
    nh, qr, r = (cfg["num_attention_heads"], cfg["q_lora_rank"],
                 cfg["kv_lora_rank"])
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    return {
        # (values, output channels) of each matrix
        "attention": [(h * qr, qr), (qr * nh * (nope + rope),
                                     nh * (nope + rope)),
                      (h * (r + rope), r + rope), (r * nh * nope, nh * nope),
                      (r * nh * v, nh * v), (nh * v * h, h)],
        "norms": 2 * h + qr + r,               # bf16 vectors of a layer
        "expert": [(h * ie, ie), (h * ie, ie), (ie * h, h)],
        "dense": [(h * i, i), (h * i, i), (i * h, h)],
        "head": [(h * cfg["vocab_size"], cfg["vocab_size"])],
        "eh": [(2 * h * h, h)],                # [embedding ; hidden] -> h
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


def drafts(cfg: dict) -> int:
    """Drafts a step verifies: ``launch.spec_k`` where the configuration
    launches with its module drafting, else 0."""
    launch = cfg.get("launch") or {}
    return int(launch.get("spec_k", 1)) if launch.get(
        "spec_decode") == "mtp" else 0


def pool_layers(cfg: dict) -> int:
    """Layers of latent entries a token leaves: the model's and, where it
    drafts, the module's."""
    return cfg["num_hidden_layers"] + (
        cfg.get("num_nextn_predict_layers", 0) if drafts(cfg) else 0)


def entry_bytes(cfg: dict) -> int:
    """Bytes ONE latent entry holds in ONE layer of the pool: the latent,
    the shared rope key, and zeros up to the next multiple of 128 lanes
    (bf16). The pool is this array alone."""
    used = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return -(-used // 128) * 128 * 2


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
    """Expert layers of the model, the ones the ``moe`` scope times: what
    ``moe_roofline`` multiplies a layer's bytes by."""
    return cfg["num_hidden_layers"] - cfg.get("first_k_dense_replace", 0)


def experts_read(cfg: dict, touched: float | None) -> float:
    """Held experts ONE expert layer reads in a step: every one it holds
    where no count is given, else the count, and a step cannot touch more
    experts than it has (nor fewer than none)."""
    held = cfg["n_routed_experts"]
    return held if touched is None else min(max(float(touched), 0.0), held)


def counted_expert_layers(cfg: dict) -> int:
    """Expert layers the program's count is a mean over, and the floor's
    count applies to: the model's and, where it drafts, the module's."""
    return expert_layers(cfg) + (
        cfg.get("num_nextn_predict_layers", 0) if drafts(cfg) else 0)


def _expert_layer(cfg: dict, quant: str | None,
                  touched: float | None = None) -> float:
    sizes = _sizes(cfg)
    return (stored(sizes["attention"], quant) + sizes["norms"] * 2
            + expert_layer_bytes(cfg, quant, experts_read(cfg, touched))
            + shared_layer_bytes(cfg, quant))


def draft_module_bytes(cfg: dict, quant: str | None,
                       touched: float | None = None) -> float:
    """Bytes ONE run of the prediction module reads in weights: its
    projection of [embedding ; hidden], its three norms, its whole expert
    layer (every held expert, or the ``touched`` a count gives) and the
    model's head, which it reads for its draft."""
    sizes = _sizes(cfg)
    return (stored(sizes["eh"], quant) + 3 * cfg["hidden_size"] * 2
            + _expert_layer(cfg, quant, touched)
            + stored(sizes["head"], quant))


def sparse_attention_counts(cfg: dict, keys: float) -> tuple[float, float]:
    """(bytes, operations) of ONE step's attention: ``keys`` (row, layer,
    key) triples in context, each entry read ONCE (``entry_bytes``) and
    scored by every head of each of the k + 1 query positions in the
    latent's space (kv_lora_rank + qk_rope_head_dim products) and weighed
    (kv_lora_rank)."""
    nh, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    per_key = nh * (2 * (r + cfg["qk_rope_head_dim"]) + 2 * r)
    return keys * entry_bytes(cfg), (drafts(cfg) + 1) * per_key * keys


def decode_step_bytes(cfg: dict, quant: str | None, tp: int, rows: float,
                      context_tokens: float, touched: float | None = None
                      ) -> float:
    if tp != 1:
        raise ValueError("the GLM-4.7-Flash share is served on one device")
    sizes = _sizes(cfg)
    h, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    dense = cfg.get("first_k_dense_replace", 0)
    per_value = 1 if quant == "int8" else 2
    k = drafts(cfg)
    model = (expert_layers(cfg) * _expert_layer(cfg, quant, touched)
             + dense * (stored(sizes["attention"], quant)
                        + sizes["norms"] * 2 + stored(sizes["dense"], quant))
             + stored(sizes["head"], quant) + h * 2)          # final norm
    module = draft_module_bytes(cfg, quant, touched) if k else 0.0
    # Entries: read once a step whatever k; a row commits 1 to k + 1.
    pool = pool_layers(cfg) * (context_tokens + rows) * entry_bytes(cfg)
    embed = (1 + 2 * k) * max(1, round(rows)) * h * per_value
    return model + module + pool + embed


def decode_step_flops(cfg: dict, tp: int, rows: float,
                      context_tokens: float) -> float:
    """What the step has to compute: k + 1 positions a row through the
    model (the latent projections, the held share of each position's chosen
    experts, the shared expert, the router, the dense feed-forward of the
    leading layer, the head), one position a row through the module and its
    head, and the attention's products over every key in context."""
    if tp != 1:
        raise ValueError("the GLM-4.7-Flash share is served on one device")
    sizes = _sizes(cfg)
    h, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    dense = cfg.get("first_k_dense_replace", 0)
    values = lambda ms: sum(v for v, _ in ms)  # noqa: E731
    held_picks = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
                  / routed_experts(cfg))
    expert = (values(sizes["attention"]) + h * routed_experts(cfg)
              + (held_picks + cfg.get("n_shared_experts", 0))
              * values(sizes["expert"]))
    per_position = ((layers - dense) * expert
                    + dense * (values(sizes["attention"])
                               + values(sizes["dense"]))
                    + values(sizes["head"]))
    k = drafts(cfg)
    module = (values(sizes["eh"]) + expert + values(sizes["head"])) if k \
        else 0.0
    _, attn_ops = sparse_attention_counts(
        cfg, pool_layers(cfg) * context_tokens)
    return 2 * rows * ((k + 1) * per_position + module) + attn_ops
